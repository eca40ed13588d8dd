"""Grouped-decode + async-loading tests: parity of the grouped path against
the per-expert reference path, O(1) expert-compute dispatches, async
double-buffered prefetch (wall-clock overlap accounting, in-flight
reservation safety), one device write of an expert for the synchronous and
staged loaders, deduplicated pending-prediction bookkeeping, and the
union-overflow / all-hard-pinned cache corners at batch > 1."""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import (CacheStarvation, EngineConfig, LRU,
                        MultidimensionalCache, OffloadEngine, PREC_HI,
                        PREC_LO, Thresholds)
from repro.core.loader import ON_DEMAND, LoadTask
from repro.models import build_model
from repro.serving.api import HobbitBackend, generate


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=4, d_model=128,
                        vocab=256)
    # ample dispatch capacity so the dense prefill never drops tokens at
    # batch > 1 (batched-vs-batch1 comparisons share prefill numerics)
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _reference(ecfg: EngineConfig) -> EngineConfig:
    """Same engine settings on the per-expert reference path (which loads
    synchronously)."""
    return dataclasses.replace(ecfg, grouped=False)


def _step_logits(m, params, ecfg, prompts, teacher):
    """Per-step logits (prefill + teacher-forced decode) through a backend."""
    be = HobbitBackend(OffloadEngine(m, params, ecfg))
    be.start_batch(prompts.shape[0], 32)
    out = [be.prefill(prompts)]
    for t in range(teacher.shape[0]):
        out.append(be.step(teacher[t]))
    return np.stack(out), be.engine


# ------------------------------------------------------------------ parity
def test_grouped_matches_per_expert_path_every_slot(setup):
    """Grouped decode (one hi GEMM + one lo dequant-GEMM per layer) must
    reproduce the per-expert reference path's logits for every batch slot,
    under mixed precision, a constrained cache and prefetch enabled."""
    m, params = setup
    ecfg = EngineConfig(hi_slots=6, lo_slots=4, thresholds=Thresholds(0.6, 0.9))
    rng = np.random.default_rng(11)
    prompts = rng.integers(0, 256, (4, 6))
    teacher = rng.integers(0, 256, (5, 4))
    lg_g, _ = _step_logits(m, params, ecfg, prompts, teacher)
    lg_r, _ = _step_logits(m, params, _reference(ecfg), prompts, teacher)
    np.testing.assert_allclose(lg_g, lg_r, atol=1e-3)


def test_grouped_step_pallas_kernels_match_xla(setup, monkeypatch):
    """Full grouped decode step with the Pallas kernel tier active
    (REPRO_KERNEL_MODE=pallas -> interpret mode on CPU): per-slot logits
    equal the XLA oracle path within tolerance, and the dispatch counters
    prove the fused kernels actually ran (auto fallback is never silent)."""
    m, params = setup
    ecfg = EngineConfig(hi_slots=6, lo_slots=4, thresholds=Thresholds(0.6, 0.9))
    rng = np.random.default_rng(21)
    prompts = rng.integers(0, 256, (3, 5))
    teacher = rng.integers(0, 256, (4, 3))
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    lg_x, _ = _step_logits(m, params, ecfg, prompts, teacher)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    lg_p, eng = _step_logits(m, params, ecfg, prompts, teacher)
    np.testing.assert_allclose(lg_p, lg_x, atol=1e-3)
    disp = eng.stats()["kernel_dispatch"]
    assert disp.get("gating_topk.pallas_interpret", 0) > 0
    assert disp.get("grouped_dequant_matmul.pallas_interpret", 0) > 0
    assert disp.get("grouped_dequant_combine.pallas_interpret", 0) > 0


def test_grouped_generate_tokens_equal_reference(setup):
    m, params = setup
    ecfg = EngineConfig(hi_slots=16, lo_slots=8)
    prompts = np.random.default_rng(12).integers(0, 256, (3, 5))
    res_g = generate(HobbitBackend(OffloadEngine(m, params, ecfg)),
                     prompts, 6, max_len=32)
    res_r = generate(HobbitBackend(OffloadEngine(m, params, _reference(ecfg))),
                     prompts, 6, max_len=32)
    np.testing.assert_array_equal(res_g.tokens, res_r.tokens)


# ------------------------------------------------ O(1) compute dispatches
def test_grouped_issues_one_dispatch_per_layer(setup):
    """Per MoE layer the grouped path issues exactly one expert-compute
    dispatch (the fused hi+lo grouped FFN), independent of batch and top_k —
    and never touches the per-expert jitted kernels."""
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=16, lo_slots=8))
    be = HobbitBackend(eng)
    prompts = np.random.default_rng(13).integers(0, 256, (4, 4))
    be.start_batch(4, 32)
    be.prefill(prompts)
    n_steps = 5
    for t in range(n_steps):
        be.step(np.full((4,), 7 + t, np.int32))
    assert eng._expert_dispatches == n_steps * eng.num_moe_layers
    # the per-expert kernels exist only on the reference path
    assert "hi" not in eng._jit_cache and "lo" not in eng._jit_cache
    assert "grouped_ffn" in eng._jit_cache


# ------------------------------------------------ async prefetch overlap
def test_async_prefetch_matches_sync_and_reports_overlap(setup):
    """The grouped path's staged prefetch yields the tokens of the
    per-expert reference, which loads synchronously, and reports its
    overlap."""
    m, params = setup
    ecfg = EngineConfig(hi_slots=8, lo_slots=4)
    prompts = np.random.default_rng(14).integers(0, 256, (2, 5))
    eng = OffloadEngine(m, params, ecfg)
    res_async = generate(HobbitBackend(eng), prompts, 6, max_len=32)
    res_sync = generate(
        HobbitBackend(OffloadEngine(m, params, _reference(ecfg))),
        prompts, 6, max_len=32)
    np.testing.assert_array_equal(res_async.tokens, res_sync.tokens)

    s = eng.stats()
    assert s["prefetch_jobs"] > 0              # async staging actually ran
    assert 0.0 <= s["overlap_fraction"] <= 1.0
    assert s["copy_s"] > 0.0 and s["load_stall_s"] >= 0.0
    assert json.loads(json.dumps(s))           # serializable end to end


def test_fetch_many_writes_all_slots(setup):
    """Batched fetch: every admitted slot is written through the commit
    scatter and counted as loader traffic."""
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    eng.start_batch(1, 8)
    items = []
    for e in range(3):
        slot, _ = eng.cache.admit((0, e), True, 0)
        items.append((0, e, PREC_HI, slot))
    before = eng.loader.n_loads[PREC_HI]
    eng._fetch_many(items)
    assert eng.loader.n_loads[PREC_HI] == before + 3
    for _, e, _, slot in items:
        np.testing.assert_allclose(np.asarray(eng.pool_hi["wi"][slot]),
                                   eng.storage_hi[0]["wi"][e], rtol=1e-6)


def test_sync_drain_writes_like_a_staged_commit(setup):
    """The synchronous loader's drain and the staging engine's on-demand
    commit write an expert the same way: the same slot holds the same bits
    in every pool tensor, and both add the same commit_shipped_bytes (the
    expert's host bytes) under one hobbit.stage.commit span."""
    m, params = setup
    ecfg = EngineConfig(hi_slots=8, lo_slots=4)
    for prec, e in ((PREC_HI, 3), (PREC_LO, 2)):
        pools, shipped = [], []
        for staged in (False, True):
            eng = OffloadEngine(m, params, ecfg)
            eng.start_batch(1, 8)
            s0 = eng.stats()
            task = LoadTask(1, e, prec, ON_DEMAND, eng.expert_bytes[prec])
            if staged:
                done = eng.scheduler.drain_on_demand([task], 1)
            else:
                eng.loader.queue.append(task)
                done = eng.loader.drain(1)
            s1 = eng.stats()
            [(_, slot)] = done
            pool = eng.pool_hi if prec == PREC_HI else eng.pool_lo
            pools.append((slot, {k: np.asarray(a[slot]).tobytes()
                                 for k, a in pool.items()}))
            shipped.append(s1["commit_shipped_bytes"]
                           - s0["commit_shipped_bytes"])
            commits = s1["spans"]["hobbit.stage.commit"]["n"] - s0["spans"][
                "hobbit.stage.commit"]["n"]
            assert commits == 1, (prec, staged)
            host = eng._stage(1, e, prec)
            assert shipped[-1] == sum(a.nbytes for a in host.values())
            assert pools[-1][1] == {k: a.tobytes() for k, a in host.items()}
        assert pools[0] == pools[1], prec
        assert shipped[0] == shipped[1], prec


def test_async_scheduler_commits_staged_weights(setup):
    """submit_prefetch reserves the slot immediately (in-flight), and
    wait(layer) lands the staged bytes in the device pool."""
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    eng.start_batch(1, 8)
    n = eng.scheduler.submit_prefetch(
        1, [0, 3], np.array([PREC_HI, PREC_HI]), current_layer=0)
    assert n == 2
    assert eng.cache.is_inflight((1, 0), True)
    eng.scheduler.wait(1)
    assert not eng.cache.is_inflight((1, 0), True)
    slot = eng.cache.lookup((1, 0), True)
    assert slot is not None
    np.testing.assert_allclose(np.asarray(eng.pool_hi["wi"][slot]),
                               eng.storage_hi[1]["wi"][0], rtol=1e-6)
    assert eng.scheduler.copy_s > 0.0


# ------------------------------------------------ prediction bookkeeping
def test_no_duplicate_pending_predictions(setup, monkeypatch):
    """Regression: the adaptive walk and the plain next-layer prediction
    used to both append a Prediction for the same (layer, slot), double-
    counting record_accuracy.  Now at most one pending entry exists per
    (layer, slot) at any point in the step."""
    m, params = setup
    dupes = []
    orig = OffloadEngine._score_pending_preds

    def spy(self, mi, tops):
        keys = [(p.layer, r) for p, _, r in self._pending_preds]
        if len(keys) != len(set(keys)):
            dupes.append(keys)
        return orig(self, mi, tops)

    monkeypatch.setattr(OffloadEngine, "_score_pending_preds", spy)
    # small cache so the adaptive walk regularly finds layer l+1 misses
    # (the condition that used to produce the duplicate entry)
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=4, lo_slots=2))
    prompts = np.random.default_rng(15).integers(0, 256, (2, 4))
    generate(HobbitBackend(eng), prompts, 5, max_len=32)
    assert not dupes
    # accuracy totals bound: <= one distance-1 sample per slot per layer
    # transition per decode step (4 decode calls x 2 slots x 3 transitions)
    c, t = eng.predictor._acc.get(1, [0, 0])
    assert t <= 4 * 2 * (eng.num_moe_layers - 1)
    assert 0 <= c <= t


# ------------------------------------ slot order within one decode layer
@pytest.fixture(scope="module")
def phi16():
    """Phi-MoE's smoke variant with its published 16 experts, top-2."""
    cfg = smoke_variant(get_config("phi-moe"), layers=2, d_model=256,
                        vocab=512)
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, num_experts=16, capacity_factor=8.0))
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _decode_logging_admissions(m, params, ecfg, steps, seed):
    """Greedy batch-2 decode of ``steps`` steps.  Returns the tokens, the
    engine and, per step, every cache admission in order as (prefetch,
    on_demand, (key, is_hi), evicted (key, is_hi) or None)."""
    eng = OffloadEngine(m, params, ecfg)
    admit, drain = eng.cache.admit, eng.scheduler.drain_on_demand
    log, in_drain = [], []

    def logged_admit(key, is_hi, layer, *, prefetch=False):
        slot, evicted = admit(key, is_hi, layer, prefetch=prefetch)
        log[-1].append((prefetch, bool(in_drain), (key, is_hi),
                        None if evicted is None else (evicted, is_hi)))
        return slot, evicted

    def logged_drain(tasks, layer):
        in_drain.append(layer)
        try:
            return drain(tasks, layer)
        finally:
            in_drain.pop()

    eng.cache.admit = logged_admit
    eng.scheduler.drain_on_demand = logged_drain
    be = HobbitBackend(eng)
    be.start_batch(2, 96)
    prompts = np.random.default_rng(seed).integers(0, 512, (2, 16))
    lg = be.prefill(prompts)
    toks = []
    for _ in range(steps):
        log.append([])
        toks.append(np.argmax(lg, -1).astype(np.int32))
        lg = be.step(toks[-1])
    return np.stack(toks), eng, log


# the cell's engine settings; a fast modeled link keeps issue-time
# precision downgrades (a timing decision) out of the comparison
TIGHT = EngineConfig(hi_slots=4, lo_slots=8, thresholds=Thresholds(0.6, 0.9),
                     link_gbps=1000.0)


def test_layer_misses_take_their_slots_before_next_layer_prefetch(phi16):
    """Tight pools (4 hi slots for a step demand of about 6): no on-demand
    admission of layer l evicts a copy prefetched for a later layer in the
    same step, so no expert is reloaded into the overflow slots, prefetches
    that find no slot are dropped, and the tokens equal the per-expert
    reference path's."""
    m, params = phi16
    toks, eng, log = _decode_logging_admissions(m, params, TIGHT, 24, 3)
    for step in log:
        prefetched = set()
        for prefetch, on_demand, entry, evicted in step:
            if prefetch:
                prefetched.add(entry)
            elif on_demand and evicted is not None:
                assert not (evicted in prefetched
                            and evicted[0][0] > entry[0][0]), step
    s = eng.stats()
    assert s["union_reloads"] == 0
    assert s["dropped_prefetch"] > 0               # the order engaged
    assert s["precision_downgrades"] == 0
    ref, _, _ = _decode_logging_admissions(m, params, _reference(TIGHT), 24,
                                           3)
    np.testing.assert_array_equal(toks, ref)


def test_pools_holding_every_expert_ship_what_the_reference_ships(phi16):
    """Bypass control: pools that hold every expert of both layers never
    evict or drop, so the order of admissions changes nothing: the staged
    path commits the bytes the synchronous reference path commits (each
    expert it needs, once) and serves the same tokens."""
    m, params = phi16
    roomy = dataclasses.replace(TIGHT, hi_slots=32, lo_slots=32)
    toks, eng, _ = _decode_logging_admissions(m, params, roomy, 12, 5)
    ref, ref_eng, _ = _decode_logging_admissions(m, params,
                                                 _reference(roomy), 12, 5)
    s, r = eng.stats(), ref_eng.stats()
    assert s["cache"]["evictions"] == 0 and s["dropped_prefetch"] == 0
    assert s["prefetch_unused"] == 0
    assert s["commit_shipped_bytes"] == r["commit_shipped_bytes"] > 0
    np.testing.assert_array_equal(toks, ref)


# ------------------------------------------------ cache corner cases
def test_union_overflow_reload_stays_correct_at_batch2(setup):
    """Cache smaller than the layer's union demand at batch 2: same-layer
    neighbours evict each other's hard-pinned experts (pathological branch),
    the engine reloads on demand, and per-slot numerics still match the
    isolated batch=1 runs."""
    m, params = setup
    ecfg = EngineConfig(hi_slots=2, lo_slots=1, thresholds=Thresholds(1.0, 1.0),
                        prefetch=False)
    prompts = np.random.default_rng(16).integers(0, 256, (2, 6))
    eng = OffloadEngine(m, params, ecfg)
    res_b = generate(HobbitBackend(eng), prompts, 5, max_len=32)
    assert eng._union_reloads > 0          # contention actually happened
    assert eng.stats()["union_reloads"] == eng._union_reloads
    assert eng.cache.stats.misses > 0
    for r in range(2):
        res_1 = generate(HobbitBackend(OffloadEngine(m, params, ecfg)),
                         prompts[r : r + 1], 5, max_len=32)
        np.testing.assert_array_equal(res_b.tokens[r], res_1.tokens[0])


def test_overflow_slots_keep_their_shapes_and_ship_only_reloads(setup):
    """The union-overflow contention of the test above over steps whose
    reload counts differ, down to none once one row is left (its two
    experts fit the two hi slots): each step ships overflow bytes exactly
    when it reloads, `grouped_ffn` and each slot scatter compile once
    whatever the count, and every row's tokens still equal its isolated
    batch-1 run's."""
    m, params = setup
    ecfg = EngineConfig(hi_slots=2, lo_slots=1, thresholds=Thresholds(1.0, 1.0),
                        prefetch=False)
    prompts = np.random.default_rng(16).integers(0, 256, (2, 6))
    eng = OffloadEngine(m, params, ecfg)
    be = HobbitBackend(eng)
    be.start_batch(2, 32)
    toks = [np.argmax(be.prefill(prompts), -1).astype(np.int32)]
    reloads, shipped, compiled = [], [], []
    for t in range(6):
        if t == 3:
            be.release(1)
        s0 = eng.stats()
        toks.append(np.argmax(be.step(toks[-1]), -1).astype(np.int32))
        s1 = eng.stats()
        reloads.append(s1["union_reloads"] - s0["union_reloads"])
        shipped.append(s1["overflow_shipped_bytes"]
                       - s0["overflow_shipped_bytes"])
        compiled.append({k: f._cache_size() for k, f in eng._jit_cache.items()})
    assert 0 in reloads and len(set(reloads)) > 1, reloads
    assert [b > 0 for b in shipped] == [n > 0 for n in reloads], (reloads,
                                                                   shipped)
    assert compiled[0]["grouped_ffn"] == 1
    # each scatter at most twice: the pools' shape and the overflow slots'
    assert all(compiled[0][("scatter", n)] <= 2 for n in (2, 4))
    assert all(c == compiled[0] for c in compiled), compiled
    toks = np.stack(toks, axis=1)                      # (2, 7)
    for r, n in ((0, 7), (1, 4)):
        res_1 = generate(HobbitBackend(OffloadEngine(m, params, ecfg)),
                         prompts[r : r + 1], n, max_len=32)
        np.testing.assert_array_equal(toks[r, :n], res_1.tokens[0, 6:])


def test_select_victim_when_everything_hard_pinned():
    """Pool smaller than one layer's pinned set: admission must still
    succeed by sacrificing a hard-pinned resident (it reloads on demand)."""
    c = MultidimensionalCache(4, hi_slots=2, lo_slots=0, weights=LRU)
    c.new_sequence()
    c.advance_token()
    c.admit((0, 0), True, 0)
    c.admit((0, 1), True, 0)
    c.pin((0, 0), True, hard=True)
    c.pin((0, 1), True, hard=True)
    slot, evicted = c.admit((0, 2), True, 0)
    assert evicted in {(0, 0), (0, 1)}
    assert c.lookup((0, 2), True) == slot


def test_inflight_reservation_blocks_eviction():
    c = MultidimensionalCache(4, hi_slots=2, lo_slots=0, weights=LRU)
    c.new_sequence()
    c.advance_token()
    s0, _ = c.admit((0, 0), True, 0)
    c.begin_inflight((0, 0), True, s0)
    c.advance_token()
    c.admit((1, 0), True, 1)
    c.advance_token()
    # (0,0) is older (LRU victim) but in flight -> (1,0) must be evicted
    _, evicted = c.admit((2, 0), True, 2)
    assert evicted == (1, 0)
    assert c.lookup((0, 0), True) == s0


def test_cache_starvation_when_every_slot_inflight():
    c = MultidimensionalCache(4, hi_slots=1, lo_slots=0, weights=LRU)
    c.new_sequence()
    c.advance_token()
    s0, _ = c.admit((0, 0), True, 0)
    c.begin_inflight((0, 0), True, s0)
    assert not c.can_admit(True)
    with pytest.raises(CacheStarvation):
        c.admit((0, 1), True, 0)
    c.end_inflight((0, 0), True)
    assert c.can_admit(True)
    slot, evicted = c.admit((0, 1), True, 0)
    assert evicted == (0, 0) and slot == s0
