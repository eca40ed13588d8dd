"""Multi-stream byte-budgeted staging engine tests: parity of the ordered
single-stream configuration against the per-expert reference path, issue-time
precision downgrades under a tight link budget, biggest-gate-first issue
reordering, in-flight reservation cancellation, idempotent engine/server
teardown, and the stats() JSON round-trip covering the new per-stream
fields (engine, simulator and BatchingServer)."""

import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from repro.core import (EngineConfig, LRU, MultidimensionalCache,
                        OffloadEngine, PREC_HI, PREC_LO, StagingEngine,
                        Thresholds)
from repro.core.loader import ON_DEMAND, DynamicExpertLoader, LoadTask
from repro.core.simulator import (HobbitSimConfig, OffloadSimulator, RTX4090,
                                  TraceLayer)
from repro.configs import get_config, smoke_variant
from repro.models import build_model
from repro.serving.api import DenseBackend, HobbitBackend, generate
from repro.serving.batching import BatchingServer, Request

HI_BYTES, LO_BYTES = 1000, 100


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=4, d_model=128,
                        vocab=256)
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _unit_engine(*, streams=2, ordered=False, link_bps=None,
                 stage_sleep=0.0, hi_slots=4, lo_slots=4, upgrade=True):
    """A StagingEngine over a fake host store: stage_fn logs its call order
    (and optionally sleeps, keeping copies in flight while the test pumps);
    commit_fn collects landed entries."""
    cache = MultidimensionalCache(4, hi_slots, lo_slots, LRU)
    cache.new_sequence()
    cache.advance_token()
    loader = DynamicExpertLoader(
        cache, Thresholds(1.0, 1.0), lambda *a: None,
        lambda prec: HI_BYTES if prec == PREC_HI else LO_BYTES)
    staged_order, committed = [], []

    def stage_fn(layer, expert, precision):
        staged_order.append((layer, expert, precision))
        if stage_sleep:
            time.sleep(stage_sleep)
        return {"layer": layer, "expert": expert}

    eng = StagingEngine(loader, stage_fn, committed.extend,
                        streams=streams, ordered=ordered, link_bps=link_bps,
                        upgrade=upgrade)
    return eng, cache, staged_order, committed


def _downgrade_one(eng, cache):
    """Drive the unit engine into one issue-time downgrade: layer 1 expert 0
    fits the budget (hi issues), expert 1 does not (hi preempted to a lo
    replacement).  Returns after both copies landed."""
    eng.set_deadline_clock(0, per_layer_s=3e-3, period_s=10e-3)
    n = eng.submit_prefetch(1, [0, 1], np.array([PREC_HI, PREC_HI]),
                            current_layer=0, gates=np.array([0.9, 0.8]))
    assert n == 2
    assert eng.precision_downgrades == 1
    eng.wait(1)
    assert cache.lookup((1, 1), False) is not None  # lo stand-in resident


# ------------------------------------------------------------- parity
def test_ordered_single_stream_matches_reference_and_default(setup):
    """`EngineConfig(streams=1, ordered=True)` is the pre-PR FIFO scheduler:
    its tokens must equal the per-expert reference path's; the default
    multi-stream budgeted config must also agree (no downgrades fire at the
    measured link rate, so issue order cannot change numerics)."""
    m, params = setup
    base = dict(hi_slots=8, lo_slots=4)
    prompts = np.random.default_rng(21).integers(0, 256, (3, 5))

    def toks(ecfg):
        return generate(HobbitBackend(OffloadEngine(m, params, ecfg)),
                        prompts, 6, max_len=32).tokens

    t_ref = toks(EngineConfig(grouped=False, **base))
    t_fifo = toks(EngineConfig(streams=1, ordered=True, **base))
    t_budg = toks(EngineConfig(**base))     # default: streams=2, budgeted
    np.testing.assert_array_equal(t_fifo, t_ref)
    np.testing.assert_array_equal(t_budg, t_ref)


# ----------------------------------------------- budgeted issue mechanics
def test_budget_preemption_downgrades_queued_hi_job():
    """A queued hi job whose bytes exceed the remaining link budget before
    its deadline is preempted: hi reservation cancelled, lo replacement
    reserved + staged, downgrade recorded for the compute path.  With the
    upgrade pass OFF this is the PR-4 per-token contract: the marker dies
    with retire_layer."""
    eng, cache, staged, committed = _unit_engine(
        link_bps=1e6, stage_sleep=0.25, upgrade=False)
    # budget window for layer 1 = 1 layer * 3 ms * 1e6 B/s * 0.5 safety =
    # 1500 bytes; per-pump stream feed = 10 ms * 1e6 = 10000 bytes, so both
    # jobs reach the issue decision while job 0 is still in flight
    eng.set_deadline_clock(0, per_layer_s=3e-3, period_s=10e-3)
    n = eng.submit_prefetch(1, [0, 1], np.array([PREC_HI, PREC_HI]),
                            current_layer=0, gates=np.array([0.9, 0.8]))
    assert n == 2
    # job 0 fit the budget (1000 <= 1500); job 1 did not (1000+1000 > 1500)
    assert eng.precision_downgrades == 1
    assert (1, 1) in eng.downgraded
    assert cache.lookup((1, 1), True) is None       # hi reservation cancelled
    assert cache.is_inflight((1, 1), False)         # lo replacement in flight
    eng.wait(1)
    assert cache.lookup((1, 0), True) is not None   # hi copy landed
    assert cache.lookup((1, 1), False) is not None  # lo replacement landed
    assert eng.serves_lo_downgrade(1, 1)
    precs = sorted(t.precision for t, _, _ in committed)
    assert precs == sorted([PREC_HI, PREC_LO])
    eng.retire_layer(1)
    assert not eng.serves_lo_downgrade(1, 1)        # one-token decision
    eng.shutdown()


# ------------------------------------------------- idle-link upgrade pass
def test_upgrade_promotes_downgraded_expert_in_place():
    """After a downgrade, the substitution persists across retire_layer
    (upgrade pass ON); once the link idles, a hi re-copy is issued for the
    lo-resident expert, lands beside the lo copy via the precision-keyed
    reservation, and serves_lo_downgrade flips off — compute switches to
    hi."""
    eng, cache, staged, committed = _unit_engine(link_bps=1e6,
                                                 stage_sleep=0.05)
    _downgrade_one(eng, cache)
    eng.retire_layer(1)
    assert eng.serves_lo_downgrade(1, 1)            # persistent substitution
    eng._pump()                                     # link idle: upgrade pass
    assert eng.upgrades == 1
    assert eng.upgrade_bytes == HI_BYTES
    assert cache.is_inflight((1, 1), True)
    assert eng.serves_lo_downgrade(1, 1)            # hi not landed yet
    eng.wait_all()
    assert cache.lookup((1, 1), True) is not None   # hi landed...
    assert cache.lookup((1, 1), False) is not None  # ...beside the lo copy
    assert not eng.serves_lo_downgrade(1, 1)        # compute now serves hi
    assert (1, 1) not in eng.lo_substituted
    precs = sorted(t.precision for t, _, _ in committed)
    assert precs == sorted([PREC_HI, PREC_HI, PREC_LO])
    eng.shutdown()


def test_staging_checks_do_not_ask_the_compute_query():
    """`serves_lo_downgrade` answers the compute path, so its answers are
    the precisions served: the staging engine's own downgrade checks (a
    prefetch for the expert, an on-demand hi miss) skip the downgraded
    expert without asking it, since the substitution can end before the
    layer computes."""
    eng, cache, staged, committed = _unit_engine(link_bps=1e6,
                                                 stage_sleep=0.05)
    _downgrade_one(eng, cache)
    eng.retire_layer(1)
    asked = []
    eng.serves_lo_downgrade = lambda *key: asked.append(key)
    assert eng.submit_prefetch(1, [1], np.array([PREC_HI]), current_layer=0,
                               gates=np.array([0.9])) == 0
    miss = LoadTask(1, 1, PREC_HI, ON_DEMAND, HI_BYTES)
    assert eng.drain_on_demand([miss], 1) == []
    assert asked == []
    eng.shutdown()


def test_upgrade_issues_only_on_idle_budget():
    """With queued deadline work pending, or a hi stream already fed to its
    budget, the upgrade pass must stay silent; it fires only once the
    pending queue drains and the stream has leftover budget."""
    eng, cache, staged, committed = _unit_engine(streams=1, stage_sleep=0.05,
                                                 link_bps=1e6, hi_slots=16)
    # feed = 10 ms * 1e6 B/s = 10000 B; deadline budget ample (no downgrades)
    eng.set_deadline_clock(0, per_layer_s=10e-3, period_s=10e-3)
    # hand-plant a landed downgrade substitution: lo resident, hi absent
    cache.admit((1, 1), False, 0)
    eng.lo_substituted.add((1, 1))
    # 12 hi jobs x 1000 B overfill the 10000 B feed: 2 stay queued
    eng.submit_prefetch(3, list(range(2, 14)), np.full(12, PREC_HI),
                        current_layer=0)
    assert eng._pending                             # deadline work queued
    assert eng.upgrades == 0                        # never on a busy link
    eng._pump()
    assert eng.upgrades == 0
    eng.wait_all()
    # hysteresis: the pass waits for TWO consecutive deadline-free pumps
    eng._pump()
    assert eng.upgrades == 0
    eng._pump()                                     # drained + idle: fires
    assert eng.upgrades == 1
    eng.wait_all()
    assert cache.lookup((1, 1), True) is not None
    eng.shutdown()


def test_upgrade_never_preempts_or_blocks_deadline_work():
    """A deadline prefetch competing with an upgrade candidate for the same
    pump is issued first (upgrades are created only after the pending queue
    empties), and wait(layer) never blocks on an in-flight upgrade
    targeting that layer."""
    from repro.core.loader import UPGRADE
    eng, cache, staged, committed = _unit_engine(link_bps=1e6,
                                                 stage_sleep=0.2)
    eng.set_deadline_clock(0, per_layer_s=3e-3, period_s=10e-3)
    cache.admit((1, 1), False, 0)
    eng.lo_substituted.add((1, 1))
    # deadline prefetch (2, 5) and the (1, 1) upgrade candidate hit the same
    # pump: the deadline job takes the hi stream and suppresses the upgrade
    # (hysteresis resets on any deadline work; a busy stream blocks it too)
    eng.submit_prefetch(2, [5], np.array([PREC_HI]), current_layer=1)
    assert eng.upgrades == 0
    eng._pump()
    eng._pump()
    assert eng.upgrades == 0                        # (2, 5) still in flight
    eng.wait(2)                                     # deadline copy lands
    eng._pump()                                     # second idle pump: fires
    assert eng.upgrades == 1
    time.sleep(0.05)                                # worker starts the copy
    hi_staged = [(lay, e) for lay, e, p in staged if p == PREC_HI]
    assert hi_staged[0] == (2, 5), hi_staged
    # wait(1) must not block on the in-flight upgrade targeting layer 1
    t0 = time.perf_counter()
    eng.wait(1)
    assert time.perf_counter() - t0 < 0.18          # no 0.2 s upgrade wait
    # structural proof wait(1) did not block: the upgrade is either still
    # in flight or was collected already-done (a loaded runner can finish
    # the 0.2 s copy before the barrier) — never waited on
    assert (any(j.task.reason == UPGRADE for j in eng._issued)
            or any(t.reason == UPGRADE for t, _, _ in committed))
    eng.wait_all()
    eng.shutdown()


def test_upgrade_fires_when_hi_copy_exceeds_layer_feed():
    """Regression: in the offload regime one hi copy often exceeds a whole
    layer-period of link bytes; the upgrade pass must still re-promote on a
    fully idle stream (the one-in-flight cap, not a feed veto, bounds its
    interference) — a feed veto would make downgrades permanent exactly
    when compute per layer << copy time, HOBBIT's own premise."""
    eng, cache, staged, committed = _unit_engine(link_bps=1e6,
                                                 stage_sleep=0.01)
    # feed = 1e6 B/s * 0.3 ms = 300 B < one hi copy (1000 B)
    eng.set_deadline_clock(0, per_layer_s=3e-4, period_s=3e-4)
    cache.admit((1, 1), False, 0)
    eng.lo_substituted.add((1, 1))
    eng._pump()
    eng._pump()                                 # two idle pumps: must fire
    assert eng.upgrades == 1
    eng.wait_all()
    assert cache.lookup((1, 1), True) is not None
    eng.shutdown()


def test_no_upgrade_keeps_pr4_per_token_semantics():
    """upgrade=False is the PR-4 parity switch: the downgrade marker dies
    with retire_layer, no hi re-copy is ever issued, and the stats counters
    stay zero."""
    eng, cache, staged, committed = _unit_engine(link_bps=1e6,
                                                 stage_sleep=0.05,
                                                 upgrade=False)
    _downgrade_one(eng, cache)
    assert eng.serves_lo_downgrade(1, 1)
    eng.retire_layer(1)
    assert not eng.serves_lo_downgrade(1, 1)        # one-token decision
    eng._pump()
    eng.wait_all()
    assert eng.upgrades == 0
    assert eng.upgrade_bytes == 0
    assert cache.lookup((1, 1), True) is None       # hi never re-issued
    eng.shutdown()


def test_ordered_engine_never_upgrades():
    """The ordered parity scheduler has no downgrades, hence nothing to
    upgrade — the flag is forced off."""
    eng, *_ = _unit_engine(streams=1, ordered=True)
    assert not eng.upgrade
    eng.shutdown()


def test_engine_upgrade_recovery_under_contention(setup):
    """Engine-level: an emulated slow link makes cold-start prefetch
    contention downgrade hi copies to lo; after the load drops (batch 4 ->
    1, stationary tokens) the idle-link pass re-issues hi copies and lands
    them beside the lo stand-ins, while --no-upgrade never upgrades."""
    from repro.quant.quantize import expert_nbytes
    m, params = setup
    d, f = m.cfg.d_model, m.cfg.moe.d_ff_expert
    link_gbps = expert_nbytes(d, f, 16) / 10e-3 / 1e9   # hi copy ~10 ms

    def serve(upgrade):
        eng = OffloadEngine(m, params, EngineConfig(
            hi_slots=8, lo_slots=6, link_gbps=link_gbps, upgrade=upgrade))
        be = HobbitBackend(eng)
        rng = np.random.default_rng(0)
        arr = rng.integers(0, 256, (4, 24))
        be.start_batch(4, 28)
        for r in range(4):
            be.join(r, arr[r, :1].astype(np.int32))
        for t in range(1, 9):                   # contention burst, batch 4
            be.step(arr[:, t].astype(np.int32))
        for r in range(1, 4):                   # load drops: idle phase
            be.release(r)
        for _t in range(9, 19):
            be.step(np.full(4, 7, np.int32))
        s = eng.stats()
        be.close()
        return s

    on = serve(True)
    off = serve(False)
    assert on["precision_downgrades"] > 0       # the burst actually contended
    assert on["upgrades"] > 0                   # idle-link recovery fired
    assert on["upgrade_bytes"] > 0
    assert off["upgrades"] == 0                 # --no-upgrade: never
    assert off["upgrade_bytes"] == 0


# ------------------------------------------------- satellite regressions
def test_drain_on_demand_empty_or_hit_only_adds_no_stall():
    """Regression: a layer with an empty (or fully-skipped) miss set must
    contribute exactly 0.0 stall — not a timer epsilon per layer."""
    from repro.core.loader import ON_DEMAND, LoadTask
    eng, cache, staged, committed = _unit_engine()
    assert eng.drain_on_demand([], 0) == []
    # resident task: skipped before the timer starts
    slot, _ = cache.admit((0, 3), True, 0)
    t = LoadTask(0, 3, PREC_HI, ON_DEMAND, HI_BYTES)
    assert eng.drain_on_demand([t], 0) == []
    assert eng.stall_s == 0.0
    eng.shutdown()


def test_pump_without_feed_estimate_issues_all_jobs():
    """Regression: before the first set_deadline_clock (or with an
    unmodeled link) there is no feed estimate; the pump must treat that as
    unlimited feed, not a one-byte threshold that serializes each stream to
    a single outstanding copy."""
    eng, cache, staged, committed = _unit_engine(streams=1, stage_sleep=0.2)
    eng.submit_prefetch(1, [0, 1, 2], np.full(3, PREC_HI), current_layer=0)
    assert len(eng._issued) == 3        # all in flight at once
    eng.wait_all()
    eng.shutdown()


def test_cancel_inflight_drops_stale_pins():
    """Regression: cancelling an in-flight (key, hi) reservation must also
    drop its pins — a downgraded-away hi key must not keep constraining
    _select_victim until the next advance_token."""
    c = MultidimensionalCache(4, hi_slots=1, lo_slots=1, weights=LRU)
    c.new_sequence()
    c.advance_token()
    s_hi, _ = c.admit((0, 7), True, 0)
    c.pin((0, 7), True, hard=True)
    c.begin_inflight((0, 7), True, s_hi)
    c.cancel_inflight((0, 7), True)
    assert ((0, 7), True) not in c.pinned
    assert ((0, 7), True) not in c.hard_pinned
    # the freed slot is immediately admittable again (no phantom hard pin)
    assert c.can_admit(True)
    s2, _ = c.admit((0, 8), True, 0)
    assert s2 == s_hi


def test_biggest_gate_issues_first_within_layer():
    """Within one deadline layer a stream issues the biggest-gate job first,
    counting the FIFO inversion as an issue_reorder."""
    eng, cache, staged, _ = _unit_engine(streams=1)
    eng.submit_prefetch(2, [0, 1], np.array([PREC_HI, PREC_HI]),
                        current_layer=0, gates=np.array([0.1, 0.9]))
    eng.wait(2)
    assert [e for _, e, _ in staged] == [1, 0]      # gate 0.9 before 0.1
    assert eng.issue_reorders >= 1
    eng.shutdown()


def test_nearest_deadline_layer_issues_first():
    """Across deadline layers the nearest layer's job overtakes an older
    queued job for a later layer.  (A modeled link with a tight feed keeps
    the later submissions queued — without a feed estimate every job now
    issues immediately, see test_pump_without_feed_estimate_issues_all_jobs.)"""
    eng, cache, staged, _ = _unit_engine(streams=1, stage_sleep=0.05,
                                         link_bps=1e4)
    # feed = 10 kB/s * 5 ms = 50 B < one lo copy (100 B): the stream is fed
    # by a single outstanding copy and later submissions stay reorderable
    eng.set_deadline_clock(0, per_layer_s=5e-3, period_s=5e-3)
    eng.submit_prefetch(3, [0], np.array([PREC_LO]), current_layer=0)
    eng.submit_prefetch(3, [1], np.array([PREC_LO]), current_layer=0)
    eng.submit_prefetch(1, [2], np.array([PREC_LO]), current_layer=0)
    # job for layer 3/expert 0 is in flight; jobs (3,1) and (1,2) are queued:
    # once the stream frees, the layer-1 job must overtake the older (3,1)
    time.sleep(0.15)
    eng._pump()
    eng.wait_all()
    assert [(lay, e) for lay, e, _ in staged] == [(3, 0), (1, 2), (3, 1)]
    assert eng.issue_reorders >= 1
    eng.shutdown()


def test_cancel_inflight_returns_slot_and_keeps_other_precision():
    """cancel_inflight drops only the (key, precision) reservation it names:
    the slot returns to the free list and a lo copy of the same expert is
    untouched (precision-keyed reservations)."""
    c = MultidimensionalCache(4, hi_slots=1, lo_slots=1, weights=LRU)
    c.new_sequence()
    c.advance_token()
    s_lo, _ = c.admit((0, 7), False, 0)
    s_hi, _ = c.admit((0, 7), True, 0)
    c.begin_inflight((0, 7), True, s_hi)
    assert c.cancel_inflight((0, 7), True) == s_hi
    assert c.lookup((0, 7), True) is None
    assert s_hi in c.hi.free                        # slot reusable
    assert c.lookup((0, 7), False) == s_lo          # lo copy untouched
    assert c.cancel_inflight((0, 7), True) is None  # idempotent


# ------------------------------------------------- prefetch use accounting
def test_prefetch_evicted_before_any_probe_counts_once():
    """A landed prefetched copy that an admission evicts before any probe
    found it counts once in prefetch_unused; evicting a copy that was
    loaded on demand counts nothing."""
    eng, cache, _, _ = _unit_engine(hi_slots=1)
    eng.submit_prefetch(1, [0], np.array([PREC_HI]), current_layer=0)
    eng.wait(1)
    _, evicted = cache.admit((0, 5), True, 0)
    assert evicted == (1, 0)
    assert cache.prefetch_unused == 1
    _, evicted = cache.admit((0, 6), True, 0)
    assert evicted == (0, 5)
    cache.new_sequence()
    assert cache.prefetch_unused == 1
    eng.shutdown()


def test_prefetch_probed_in_flight_counts_as_used():
    """A probe that finds the prefetched slot while its copy is still in
    flight is a use: neither the later eviction nor a flush counts it."""
    eng, cache, _, _ = _unit_engine(hi_slots=1, stage_sleep=0.05)
    eng.submit_prefetch(1, [0], np.array([PREC_HI]), current_layer=0)
    assert cache.is_inflight((1, 0), True)
    assert cache.probe((1, 0), True) is not None
    eng.wait(1)
    cache.admit((0, 5), True, 0)
    cache.new_sequence()
    assert cache.prefetch_unused == 0
    eng.shutdown()


def test_prefetch_cancelled_by_a_downgrade_counts_once():
    """The hi reservation a downgrade cancels counts once; the lo stand-in
    the compute path serves in its place (``note_use``) and the hi copy a
    probe found count nothing, even after a flush."""
    eng, cache, _, _ = _unit_engine(link_bps=1e6, stage_sleep=0.05)
    _downgrade_one(eng, cache)
    assert cache.prefetch_unused == 1
    assert cache.probe((1, 0), True) is not None
    cache.note_use((1, 1), False)
    cache.new_sequence()
    assert cache.prefetch_unused == 1
    eng.shutdown()


def test_prefetch_flushed_at_new_sequence_counts_once():
    """A prefetched copy still unprobed when the sequence ends counts once,
    at the first new_sequence only."""
    eng, cache, _, _ = _unit_engine()
    eng.submit_prefetch(1, [0, 1], np.array([PREC_HI, PREC_LO]),
                        current_layer=0)
    eng.flush()
    assert cache.probe((1, 0), True) is not None
    cache.new_sequence()
    assert cache.prefetch_unused == 1               # (1, 1) lo, never probed
    cache.new_sequence()
    assert cache.prefetch_unused == 1
    eng.shutdown()


# ------------------------------------------------------------- teardown
def test_engine_close_idempotent_and_step_raises(setup):
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    generate(HobbitBackend(eng), np.array([[1, 2, 3]]), 3, max_len=16)
    eng.close()
    eng.close()                                     # second close: no-op
    with pytest.raises(RuntimeError, match="closed"):
        eng.decode_step_batch(np.array([1], np.int32))
    with pytest.raises(RuntimeError, match="closed"):
        eng.start_batch(1, 8)


def test_batching_server_close_releases_staging_threads(setup):
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    rng = np.random.default_rng(3)
    with BatchingServer(HobbitBackend(eng), max_batch=2, max_len=32) as srv:
        for i in range(2):
            srv.submit(Request(rid=i, prompt=rng.integers(0, 256, 4),
                               max_new_tokens=3))
        srv.run()
        assert len(srv.completed) == 2
    # scope exit closed the backend -> engine closed, worker threads released
    assert eng._closed
    assert not eng.scheduler._finalizer.alive
    srv.close()                                     # idempotent


def test_dense_backend_close_is_noop(setup):
    cfg = smoke_variant(get_config("granite-3-2b"), layers=2, d_model=64,
                        vocab=128)
    cfg = dataclasses.replace(cfg, dtype="float32")
    m = build_model(cfg)
    be = DenseBackend(m, m.init(jax.random.PRNGKey(0)))
    be.close()
    be.close()
    be.start_batch(1, 8)                            # still usable


# ------------------------------------------------------- stats round-trip
def _roundtrip_same_keys(stats: dict) -> dict:
    """json round-trip must preserve the exact key set (serving contract)."""
    back = json.loads(json.dumps(stats))
    assert set(back) == set(stats)
    return back


NEW_FIELDS = ("per_stream_bytes", "issue_reorders", "precision_downgrades",
              "upgrades", "upgrade_bytes", "served_lo_expert_steps",
              "link_utilization")


def test_engine_stats_json_roundtrip_with_stream_fields(setup):
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    generate(HobbitBackend(eng), np.array([[1, 2, 3]]), 4, max_len=16)
    s = eng.stats()
    back = _roundtrip_same_keys(s)
    for f in NEW_FIELDS + ("streams", "link_gbps", "prefetch_unused",
                           "dropped_prefetch"):
        assert f in back, f
    assert back["streams"] == 2
    assert isinstance(back["per_stream_bytes"], list)
    assert len(back["per_stream_bytes"]) == 2
    assert sum(back["per_stream_bytes"]) > 0        # prefetch traffic issued
    eng.close()


def test_simulator_stats_json_roundtrip_with_stream_fields():
    rng = np.random.default_rng(5)
    trace = []
    for _ in range(6):
        token = []
        for _li in range(3):
            g = np.sort(rng.random(2))[::-1]
            token.append(TraceLayer(experts=rng.permutation(8)[:2].tolist(),
                                    gate_vals=g,
                                    pred_experts=rng.permutation(8)[:2].tolist(),
                                    pred_gate_vals=np.sort(rng.random(2))[::-1]))
        trace.append(token)
    cfg = HobbitSimConfig(hi_slots=4, lo_slots=2, hi_bytes=10_000_000,
                          lo_bytes=2_500_000, streams=2, ordered=False)
    res = OffloadSimulator("hobbit", 3, RTX4090, cfg).run(trace)
    ser = {k: v for k, v in res.items() if k != "stats"}   # CacheStats object
    back = _roundtrip_same_keys(ser)
    for f in NEW_FIELDS:
        assert f in back, f
    assert len(back["per_stream_bytes"]) == 2
    assert back["cache"]["hits"] == res["stats"].hits      # dict mirror


def test_simulator_single_stream_default_unchanged():
    """streams=1/ordered=True (the default) must reproduce the single-DMA
    timeline: one stream, all bytes on it, no downgrades or reorders."""
    rng = np.random.default_rng(6)
    trace = [[TraceLayer(experts=[0, 1], gate_vals=np.array([0.6, 0.3]),
                         pred_experts=[2, 3],
                         pred_gate_vals=np.array([0.5, 0.2]))
              for _ in range(2)] for _ in range(4)]
    cfg = HobbitSimConfig(hi_slots=4, lo_slots=2, hi_bytes=1_000_000,
                          lo_bytes=250_000)
    res = OffloadSimulator("hobbit", 2, RTX4090, cfg).run(trace)
    assert len(res["per_stream_bytes"]) == 1
    assert res["precision_downgrades"] == 0
    assert res["issue_reorders"] == 0


def test_server_stats_json_roundtrip_with_stream_fields(setup):
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(hi_slots=8, lo_slots=4))
    rng = np.random.default_rng(7)
    with BatchingServer(HobbitBackend(eng), max_batch=2, max_len=32) as srv:
        for i in range(2):
            srv.submit(Request(rid=i, prompt=rng.integers(0, 256, 4),
                               max_new_tokens=3))
        srv.run()
        s = srv.stats()
    back = _roundtrip_same_keys(s)
    for f in ("precision_downgrades", "issue_reorders", "link_utilization",
              "mean_precision_downgrades", "upgrades", "upgrade_bytes",
              "served_lo_expert_steps", "mean_served_lo"):
        assert f in back, f
    for f in NEW_FIELDS:
        assert f in back["backend"], f
