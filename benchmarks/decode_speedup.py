"""Fig. 14 reproduction: end-to-end decode throughput of HOBBIT vs the
paper's baseline systems, trace-driven (real routing traces from the trained
models; hardware cost models for the RTX 4090 and Jetson Orin groups) —
plus a *wall-clock* section measuring the grouped batched decode path
(one hi GEMM + one lo dequant-GEMM per layer, async double-buffered
prefetch) against the per-expert reference path on this host.

System mapping (paper -> simulator):
  Llama.cpp (LL)        -> dense_layerwise (streams whole layers)
  MoE-Offloading (MO)   -> on_demand (LRU cache, fp16 on miss)
  MoE-Infinity (MI)     -> prefetch_lru (LRU + next-layer fp16 prefetch)
  HOBBIT (HB)           -> hobbit (mixed precision + adaptive prefetch +
                           multidimensional cache)

Expert byte sizes use the paper's full-scale models (Mixtral-8x7B /
Phi-MoE dims) so the simulated latencies are full-scale, while the routing
structure comes from the trained smoke models.

CLI:  PYTHONPATH=src:. python -m benchmarks.decode_speedup [--smoke]
  --smoke runs one model, fewer sequences/steps — the CI configuration that
  exercises the grouped batched path on every PR.
"""

from __future__ import annotations

import dataclasses as _dc
import time

import numpy as np

from benchmarks import common
from repro.core import (EngineConfig, HobbitSimConfig, OffloadEngine,
                        simulate_systems)
from repro.core.simulator import JETSON_ORIN, RTX4090
from repro.core.scoring import PREC_HI, precision_decisions
from repro.quant.quantize import expert_nbytes

FULL_DIMS = {
    "mixtral-smoke": (4096, 14336),   # Mixtral-8x7B expert dims
    "phi-smoke": (4096, 6400),        # Phi-MoE expert dims
}


def _wall_clock_decode(model, params, seqs, ecfg, *, steps, warm=1):
    """Teacher-forced batched decode wall clock through the serving API
    (batch = len(seqs)); returns (tok_per_s, engine_stats)."""
    from repro.serving.api import HobbitBackend

    eng = OffloadEngine(model, params, ecfg)
    backend = HobbitBackend(eng)
    arr = np.stack([np.asarray(s, np.int64) for s in seqs])
    b = arr.shape[0]
    backend.start_batch(b, steps + warm + 8)
    for r in range(b):
        backend.join(r, arr[r, :1].astype(np.int32))
    for t in range(1, warm + 1):
        backend.step(arr[:, t].astype(np.int32))  # warm the jit caches
    t0 = time.perf_counter()
    for t in range(warm + 1, steps + warm + 1):
        backend.step(arr[:, t].astype(np.int32))
    dt = time.perf_counter() - t0
    stats = eng.stats()
    backend.close()                               # release staging threads
    return b * steps / dt, stats


def wall_clock_rows(kind, model, params, *, batch=4, steps=24):
    """Grouped vs per-expert reference decode wall clock at batch >= 4."""
    seqs = common.eval_token_stream(batch)
    e = model.cfg.moe.num_experts
    n_entities = model.cfg.num_layers * e
    kw = dict(hi_slots=max(8, n_entities // 3),
              lo_slots=max(4, n_entities // 6), prefetch_p=2)
    grouped, gstats = _wall_clock_decode(
        model, params, seqs, EngineConfig(**kw), steps=steps)
    ref, _ = _wall_clock_decode(
        model, params, seqs,
        EngineConfig(grouped=False, **kw), steps=steps)
    return [
        (f"wallclock_decode_tok_s[{kind}][b{batch}][grouped]",
         round(grouped, 2), "tok/s (this host, batched grouped path)"),
        (f"wallclock_decode_tok_s[{kind}][b{batch}][per_expert]",
         round(ref, 2), "tok/s (this host, per-expert reference path)"),
        (f"wallclock_grouped_speedup[{kind}][b{batch}]",
         round(grouped / ref, 2), "grouped vs per-expert, same numerics"),
        (f"wallclock_overlap_fraction[{kind}][b{batch}]",
         round(gstats["overlap_fraction"], 3),
         "share of prefetch copy time hidden behind compute"),
        (f"wallclock_load_stall_s[{kind}][b{batch}]",
         round(gstats["load_stall_s"], 4), "loading time on critical path"),
    ]


def contended_link_rows(kind, model, params, *, smoke, batch=4):
    """Contended-link section: a tight expert cache plus a slow *emulated*
    H2D link (copies occupy their stream for bytes/link seconds), comparing
    1-stream FIFO staging (`EngineConfig(streams=1, ordered=True)` — the
    PR-2 parity scheduler) against multi-stream byte-budgeted issue (the
    StagingEngine default: one hi- + one lo-precision stream, biggest-gate-
    first within the nearest-deadline layer, queued hi copies downgraded to
    lo when the link budget can't land them in time).  The row to watch is
    `contended_stall_ratio` — budgeted staging must put measurably less
    loading time on the critical path (CI gates it via tools/check_bench.py
    against benchmarks/baseline.json).

    Note the emulation models each stream as its own copy engine (real GPUs
    expose several), so the budgeted arm's win combines extra copy
    concurrency WITH the issue policy; the `contended_precision_downgrades`
    and `contended_issue_reorders` invariants pin the policy itself — a
    regression that silently disables budgeted issue fails those gates even
    if the second stream alone keeps the stall ratio low."""
    cfg = model.cfg
    d, f = cfg.d_model, cfg.moe.d_ff_expert
    hi_b = expert_nbytes(d, f, 16)
    # link sized so ONE hi copy costs ~10 ms — several× a smoke layer's
    # compute, so queued hi copies genuinely contend for the per-layer link
    # window and the budget preemption has real work to do
    link_gbps = hi_b / 10e-3 / 1e9
    e = cfg.moe.num_experts
    n_entities = cfg.num_layers * e
    kw = dict(hi_slots=max(4, n_entities // 3),
              lo_slots=max(3, n_entities // 6),
              prefetch_p=2, link_gbps=link_gbps)
    steps = 8 if smoke else 24
    seqs = common.eval_token_stream(batch)
    fifo, fstats = _wall_clock_decode(
        model, params, seqs, EngineConfig(streams=1, ordered=True, **kw),
        steps=steps, warm=2)
    budg, bstats = _wall_clock_decode(
        model, params, seqs, EngineConfig(streams=2, ordered=False, **kw),
        steps=steps, warm=2)
    ratio = bstats["load_stall_s"] / max(fstats["load_stall_s"], 1e-9)
    return [
        (f"contended_link_gbps[{kind}]", round(link_gbps, 4),
         "emulated H2D link (one hi copy ~10 ms)"),
        (f"contended_load_stall_s[{kind}][fifo]",
         round(fstats["load_stall_s"], 4),
         "loading on the critical path, 1-stream FIFO staging"),
        (f"contended_load_stall_s[{kind}][budgeted]",
         round(bstats["load_stall_s"], 4),
         "same workload, multi-stream byte-budgeted staging"),
        (f"contended_stall_ratio[{kind}]", round(ratio, 3),
         "budgeted/fifo stall (CI gate: must stay < 1)"),
        (f"contended_decode_tok_s[{kind}][fifo]", round(fifo, 2),
         "tok/s under the emulated link, FIFO"),
        (f"contended_decode_tok_s[{kind}][budgeted]", round(budg, 2),
         "tok/s under the emulated link, budgeted"),
        (f"contended_precision_downgrades[{kind}]",
         bstats["precision_downgrades"],
         "queued hi copies preempted to lo at issue time"),
        (f"contended_issue_reorders[{kind}]", bstats["issue_reorders"],
         "jobs issued ahead of an older queued job"),
        (f"contended_link_utilization[{kind}][fifo]",
         round(fstats["link_utilization"], 3),
         "share of the staging window the modeled link was busy"),
        (f"contended_link_utilization[{kind}][budgeted]",
         round(bstats["link_utilization"], 3),
         "same, budgeted (downgrades shed queued bytes)"),
    ]


def upgrade_recovery_rows(kind, model, params, *, smoke):
    """Idle-link upgrade recovery: a contention burst (batch 4, tight hi
    pool, ~10 ms emulated hi copy) preempts queued hi prefetches to lo; the
    load then drops to one slot decoding a stationary token stream (the
    post-burst idle phase), and the upgrade pass must re-promote every
    downgraded hot expert — the served-lo fraction over the final quarter
    decays to ~0 (`upgrade_recovery_served_lo_final_fraction`, CI-gated)
    while upgrades-off keeps re-downgrading the same hot experts forever
    (the permanent-quantization failure mode this pass exists to prevent).

    Wall-clock stall on this host swings 20-40% with machine load, so the
    acceptance gate "upgrades-on stall <= 1.05x upgrades-off" is enforced on
    the *simulator's* deterministic per-stream timeline (same idle-link
    upgrade rule, `sim_upgrade_stall_ratio`); the wall-clock stalls and
    their ratio are reported as informational rows."""
    from repro.serving.api import HobbitBackend

    cfg = model.cfg
    d, f = cfg.d_model, cfg.moe.d_ff_expert
    hi_b = expert_nbytes(d, f, 16)
    link_gbps = hi_b / 10e-3 / 1e9      # one hi copy ~10 ms
    burst, idle = (10, 14) if smoke else (12, 18)
    window = max(1, idle // 4)          # final quarter of the idle phase:
    #                                     shared by the served-lo numerator
    #                                     and the hi-decision denominator
    k, n_moe = cfg.moe.top_k, sum(cfg.layer_is_moe())
    # the hi pool must hold the single-slot idle-phase working set (k experts
    # per MoE layer) with a little headroom — but stays far below the burst's
    # batch-4 union demand, so the burst genuinely contends
    hi_slots = k * n_moe + 4
    lo_slots = max(4, k * n_moe // 2)

    def serve(upgrade):
        eng = OffloadEngine(model, params, EngineConfig(
            hi_slots=hi_slots, lo_slots=lo_slots, prefetch_p=2,
            link_gbps=link_gbps, upgrade=upgrade))
        backend = HobbitBackend(eng)
        rng = np.random.default_rng(0)
        steps = burst + idle
        arr = rng.integers(0, cfg.vocab_size, (4, steps + 4))
        backend.start_batch(4, steps + 8)
        for r in range(4):
            backend.join(r, arr[r, :1].astype(np.int32))
        per_step, last = [], 0
        for t in range(1, steps + 1):
            if t == burst + 1:
                for r in range(1, 4):   # the burst ends: load drops to 1 slot
                    backend.release(r)
            tok = arr[:, t] if t <= burst else np.full(4, 7)
            backend.step(tok.astype(np.int32))
            s = eng.stats()
            per_step.append(s["served_lo_expert_steps"] - last)
            last = s["served_lo_expert_steps"]
        stats = eng.stats()
        # exact hi-decided expert-steps of the final window, recomputed from
        # the routing trace (one trace entry per idle step: single live row)
        hi_final = sum(
            int((precision_decisions(np.asarray(tl.gate_vals),
                                     eng.loader.th) == PREC_HI).sum())
            for token in eng.trace[-window:] for tl in token)
        backend.close()
        return stats, per_step, hi_final

    on, per_on, hi_final = serve(True)
    off, per_off, _ = serve(False)
    # denominator = ACTUAL hi decisions in the same window (lo/skip
    # decisions must not dilute the recovery gate)
    final_fraction = sum(per_on[-window:]) / max(hi_final, 1)
    ratio = on["load_stall_s"] / max(off["load_stall_s"], 1e-9)
    rows = [
        (f"upgrade_recovery_link_gbps[{kind}]", round(link_gbps, 4),
         "emulated H2D link (one hi copy ~10 ms)"),
        (f"upgrade_recovery_downgrades[{kind}]", on["precision_downgrades"],
         "hi prefetches preempted to lo during the burst (upgrades on)"),
        (f"upgrade_recovery_upgrades[{kind}]", on["upgrades"],
         "idle-link hi re-copies issued (CI gate: >= 1)"),
        (f"upgrade_recovery_upgrade_bytes[{kind}]", on["upgrade_bytes"],
         "bytes those re-copies moved (never counted against deadlines)"),
        (f"upgrade_recovery_served_lo[{kind}][on]",
         on["served_lo_expert_steps"],
         "lo-for-hi expert-steps, upgrades on (transient exposure)"),
        (f"upgrade_recovery_served_lo[{kind}][off]",
         off["served_lo_expert_steps"],
         "same, upgrades off (PR-4 per-token downgrade semantics)"),
        (f"upgrade_recovery_served_lo_final_fraction[{kind}]",
         round(final_fraction, 4),
         "served-lo share of hi decisions over the final quarter "
         "(CI gate: ~0 — every downgraded hot expert recovered)"),
        (f"upgrade_recovery_load_stall_s[{kind}][on]",
         round(on["load_stall_s"], 4),
         "wall-clock stall, upgrades on (informational: host-load noisy)"),
        (f"upgrade_recovery_load_stall_s[{kind}][off]",
         round(off["load_stall_s"], 4), "same, upgrades off"),
        (f"upgrade_recovery_stall_ratio[{kind}]", round(ratio, 3),
         "on/off wall stall (informational; the deterministic gate is "
         "sim_upgrade_stall_ratio)"),
    ]
    rows.extend(_sim_upgrade_rows())
    return rows


def _sim_upgrade_rows():
    """Deterministic counterpart of the wall-clock recovery section on the
    simulator's per-stream timeline (same idle-link upgrade rule as
    `StagingEngine._pump_upgrades`): a 12-token rotating burst queues two
    ~0.8-compute-window hi transfers per layer (the second always misses the
    budget and downgrades), then 16 stationary tokens reuse one hot expert
    set.  No wall clock anywhere, so the <= 1.05x stall-ratio acceptance
    gate holds exactly on any machine."""
    from repro.core.simulator import (HardwareModel, OffloadSimulator,
                                      TraceLayer)

    L, E = 4, 8
    hw = HardwareModel("upgrade-bench", link_gbps=1.0,
                       compute_s_per_layer=3e-3)
    hi_b = int(0.8 * hw.compute_s_per_layer * hw.link_gbps * 1e9)
    lo_b = hi_b // 8
    g = np.array([0.5, 0.45])           # both selections decide hi (Eq. 2)

    def tok(experts, preds):
        return [TraceLayer(experts=list(experts[li]), gate_vals=g,
                           pred_experts=list(preds[li]),
                           pred_gate_vals=g) for li in range(L)]

    burst, idle = 12, 16
    rot = lambda t: [[(2 * t + li) % E, (2 * t + li + 1) % E]  # noqa: E731
                     for li in range(L)]
    stationary = [[0, 1]] * L
    trace = []
    for t in range(burst):
        trace.append(tok(rot(t), rot(t + 1) if t + 1 < burst else stationary))
    for _ in range(idle):
        trace.append(tok(stationary, stationary))

    def sim(upgrade, n=None):
        cfg = HobbitSimConfig(hi_slots=10, lo_slots=8, hi_bytes=hi_b,
                              lo_bytes=lo_b, streams=2, ordered=False,
                              upgrade=upgrade)
        return OffloadSimulator("hobbit", L, hw, cfg).run(
            trace if n is None else trace[:n])

    on, off = sim(True), sim(False)
    # served-lo accrued over the last 4 stationary tokens (delta vs prefix)
    tail = (on["served_lo_expert_steps"]
            - sim(True, len(trace) - 4)["served_lo_expert_steps"])
    ratio = on["load_stall_s"] / max(off["load_stall_s"], 1e-12)
    return [
        ("sim_upgrade_downgrades[synthetic]", on["precision_downgrades"],
         "simulated issue-time downgrades during the burst"),
        ("sim_upgrade_upgrades[synthetic]", on["upgrades"],
         "simulated idle-link hi re-copies (CI gate: >= 1)"),
        ("sim_upgrade_served_lo[synthetic]", on["served_lo_expert_steps"],
         "simulated lo-for-hi expert-steps before recovery"),
        ("sim_upgrade_served_lo_tail[synthetic]", tail,
         "served-lo over the last 4 stationary tokens (CI gate: 0)"),
        ("sim_upgrade_stall_ratio[synthetic]", round(ratio, 4),
         "upgrades-on/off stall, deterministic timeline "
         "(CI gate: <= 1.05; upgrades must ride idle link time only)"),
    ]


def mixed_length_serving_rows(kind, model, params, *, smoke):
    """Continuous serving of a mixed-length workload (prompts 16-512 tokens)
    under a FIXED device KV budget: the dense allocator charges every slot
    max_len up front, so the budget caps it at `budget_slots` concurrent
    requests; the paged allocator (same bytes, 64-token pages) lets short
    requests pack many more slots.  The row to watch is
    `serving_occupancy_gain` — sustained concurrent-slot occupancy of paged
    vs dense (acceptance target >= 1.5x)."""
    from repro.serving.api import DenseBackend
    from repro.serving.batching import BatchingServer, Request

    page, max_len = 64, 576             # 512-token prompts + decode headroom
    budget_slots = 4                    # dense slots the KV budget affords
    pool_pages = budget_slots * (-(-max_len // page))   # same byte budget
    plens = [16, 32, 512, 64, 48, 96, 24, 128, 16, 32, 64, 48]
    n_req = 12 if smoke else 24
    new_toks = 12
    vocab = model.cfg.vocab_size

    def workload():
        rng = np.random.default_rng(13)
        return [Request(rid=i, prompt=rng.integers(0, vocab,
                                                   plens[i % len(plens)]),
                        max_new_tokens=new_toks) for i in range(n_req)]

    def serve(paged):
        be = DenseBackend(model, params, paged=paged, page_size=page,
                          kv_pages=pool_pages if paged else None)
        srv = BatchingServer(be, max_batch=3 * budget_slots if paged
                             else budget_slots, max_len=max_len, admit_k=6)
        for r in workload():
            srv.submit(r)
        t0 = time.perf_counter()
        srv.run()
        dt = time.perf_counter() - t0
        return srv.stats(), dt

    dense, dt_d = serve(paged=False)
    paged, dt_p = serve(paged=True)
    gain = paged["mean_occupancy"] / dense["mean_occupancy"]
    return [
        (f"serving_kv_budget[{kind}]", pool_pages,
         f"KV pages ({page} tok) = {budget_slots} dense slots @ {max_len}"),
        (f"serving_occupancy[{kind}][dense]",
         round(dense["mean_occupancy"], 2),
         f"mean live slots/step, dense (B,max_len) allocator, cap {budget_slots}"),
        (f"serving_occupancy[{kind}][paged]",
         round(paged["mean_occupancy"], 2),
         "mean live slots/step, paged pool, same KV bytes"),
        (f"serving_occupancy_gain[{kind}]", round(gain, 2),
         "paged vs dense sustained occupancy (target >= 1.5x)"),
        (f"serving_admission_wait_s[{kind}][dense]",
         round(dense["admission_wait_s"], 3), "submit -> first token, dense"),
        (f"serving_admission_wait_s[{kind}][paged]",
         round(paged["admission_wait_s"], 3), "submit -> first token, paged"),
        (f"serving_wall_s[{kind}][dense]", round(dt_d, 2),
         f"{n_req} mixed-length requests end to end"),
        (f"serving_wall_s[{kind}][paged]", round(dt_p, 2),
         f"{n_req} mixed-length requests end to end"),
    ]


def shared_prefix_serving_rows(kind, model, params, *, smoke):
    """Continuous serving of a shared-prefix workload (every request opens
    with the same 256-token system prompt) under a FIXED paged-KV budget,
    prefix sharing on vs off.  With sharing, admissions alias the system
    prompt's resident pages instead of re-prefilling and re-storing them, so
    the same pool sustains far more concurrent slots —
    `prefix_sharing_occupancy_gain` (CI gate: >= 1.5x) — while the sampled
    tokens stay identical (`prefix_sharing_tokens_equal`).  One request
    repeats another's prompt exactly, so its first decode write must
    copy-on-write the shared trailing page (`prefix_sharing_cow_copies`,
    CI gate: >= 1)."""
    from repro.serving.api import DenseBackend
    from repro.serving.batching import BatchingServer, Request

    page, max_len = 64, 320
    pool_pages = 10                     # = 2 unshared requests' full budget
    sys_len, suf_len, new_toks = 256, 16, 12
    n_req = 8 if smoke else 16
    vocab = model.cfg.vocab_size

    def workload():
        rng = np.random.default_rng(17)
        sys_p = rng.integers(0, vocab, sys_len)
        reqs = [Request(rid=i,
                        prompt=np.concatenate(
                            [sys_p, rng.integers(0, vocab, suf_len)]),
                        max_new_tokens=new_toks) for i in range(n_req)]
        # rid=1 repeats rid=0's prompt verbatim: a whole-prompt alias whose
        # first decode write lands on the shared trailing page -> one COW
        reqs[1] = Request(rid=1, prompt=reqs[0].prompt.copy(),
                          max_new_tokens=new_toks)
        return reqs

    def serve(sharing):
        be = DenseBackend(model, params, paged=True, page_size=page,
                          kv_pages=pool_pages, prefix_sharing=sharing)
        # admit_k=1 so each prompt is registered before the next admission
        # matches against it (the serving prefix-cache steady state)
        srv = BatchingServer(be, max_batch=n_req, max_len=max_len,
                             admit_k=1)
        for r in workload():
            srv.submit(r)
        t0 = time.perf_counter()
        srv.run()
        dt = time.perf_counter() - t0
        outs = {r.rid: r.output for r in srv.completed}
        return srv.stats(), outs, dt

    plain, outs_p, dt_p = serve(sharing=False)
    shared, outs_s, dt_s = serve(sharing=True)
    tokens_equal = int(len(outs_p) == len(outs_s) == n_req and all(
        np.array_equal(outs_p[r], outs_s[r]) for r in outs_p))
    gain = shared["mean_occupancy"] / plain["mean_occupancy"]
    return [
        (f"prefix_sharing_kv_budget[{kind}]", pool_pages,
         f"KV pages ({page} tok) = 2 unshared {sys_len}+{suf_len}-token "
         "requests"),
        (f"prefix_sharing_occupancy[{kind}][off]",
         round(plain["mean_occupancy"], 2),
         "mean live slots/step, paged pool, no sharing"),
        (f"prefix_sharing_occupancy[{kind}][on]",
         round(shared["mean_occupancy"], 2),
         "mean live slots/step, same pool, radix prefix cache on"),
        (f"prefix_sharing_occupancy_gain[{kind}]", round(gain, 2),
         "sharing-on vs sharing-off sustained occupancy (CI gate: >= 1.5x)"),
        (f"prefix_sharing_hit_tokens[{kind}]",
         shared["backend"].get("prefix_hit_tokens", 0),
         "prompt tokens served from aliased pages instead of prefill"),
        (f"prefix_sharing_cow_copies[{kind}]",
         shared["backend"].get("cow_copies", 0),
         "first-divergent-write page copies (CI gate: >= 1)"),
        (f"prefix_sharing_tokens_equal[{kind}]", tokens_equal,
         "1 iff every request's sampled tokens match the unshared run "
         "(CI gate: >= 1)"),
        (f"prefix_sharing_wall_s[{kind}][off]", round(dt_p, 2),
         f"{n_req} shared-prefix requests end to end"),
        (f"prefix_sharing_wall_s[{kind}][on]", round(dt_s, 2),
         f"{n_req} shared-prefix requests end to end"),
    ]


def slo_scheduling_rows():
    """Deterministic SLO-aware vs FIFO scheduling under a bursty mixed
    workload on the simulator's virtual-clock serving timeline
    (`core.simulator.ServingTimeline` — same urgency ordering, aging bound
    and preempt-margin rule as the live `BatchingServer`): long batch
    requests (priority 0, no SLO) share 3 slots and a 1024-token KV budget
    with short interactive requests (priority 2, 1.5 s TTFT SLO) arriving
    in 6x Poisson bursts.  FIFO head-of-line-blocks the interactive class
    behind long prefills; the SLO policy reorders admission by urgency and
    preempts a low-priority decode when the top request cannot fit,
    snapshotting the victim's progress and requeueing it.  No wall clock
    anywhere, so the >= 1.3x attainment-gain acceptance gate holds exactly
    on any machine; the aging bound guarantees the requeued batch requests
    still finish (`slo_starved` CI gate: 0)."""
    from repro.core.simulator import ServingTimeline, TimelineConfig
    from repro.serving.workload import (RequestClass, WorkloadConfig,
                                        generate_workload)

    cfg = WorkloadConfig(
        classes=(
            RequestClass("batch", weight=1.0, priority=0,
                         prompt_tokens=(192, 256), new_tokens=(48, 64)),
            RequestClass("interactive", weight=1.0, priority=2,
                         ttft_slo_s=1.5, prompt_tokens=(16, 48),
                         new_tokens=(8, 16), shared_prefix=True),
        ),
        num_requests=24, arrival_rate=2.0, burst_factor=6.0,
        burst_every_s=6.0, burst_len_s=1.5, seed=7)
    trace = generate_workload(cfg)

    def sim(policy):
        tc = TimelineConfig(slots=3, kv_tokens=1024, prefill_tok_s=2048.0,
                            decode_step_s=0.05, policy=policy)
        return ServingTimeline(tc).run(trace)

    fifo, slo = sim("fifo"), sim("slo")
    gain = slo["slo_attainment"] / max(fifo["slo_attainment"], 1e-9)
    return [
        ("slo_attainment[sim-burst][fifo]", round(fifo["slo_attainment"], 3),
         "share of SLO-declaring requests meeting TTFT/TPOT, FIFO admission"),
        ("slo_attainment[sim-burst][slo]", round(slo["slo_attainment"], 3),
         "same trace, SLO-aware admission + preemption"),
        ("slo_attainment_gain[sim-burst]", round(gain, 3),
         "SLO-aware vs FIFO attainment (CI gate: >= 1.3x)"),
        ("slo_p99_ttft_s[sim-burst][fifo]", round(fifo["p99_ttft_s"], 3),
         "p99 submit -> first token, FIFO"),
        ("slo_p99_ttft_s[sim-burst][slo]", round(slo["p99_ttft_s"], 3),
         "same, SLO-aware"),
        ("slo_preemptions[sim-burst]", slo["preemptions"],
         "pause-and-requeue evictions issued (CI gate: >= 1)"),
        ("slo_starved[sim-burst]", slo["starved"],
         "requests waiting past the aging bound (CI gate: 0)"),
        ("slo_completed[sim-burst][fifo]", fifo["completed"],
         "requests finished under FIFO (both policies must complete all)"),
        ("slo_completed[sim-burst][slo]", slo["completed"],
         "requests finished under SLO-aware scheduling"),
    ]


def run(smoke: bool = False):
    rows = []
    rows.extend(slo_scheduling_rows())      # model-free: runs in smoke too
    kinds = ("mixtral-smoke",) if smoke else ("mixtral-smoke", "phi-smoke")
    for kind in kinds:
        model, params = common.get_trained(kind)
        rows.extend(wall_clock_rows(kind, model, params, batch=4,
                                    steps=8 if smoke else 24))
        rows.extend(contended_link_rows(kind, model, params, smoke=smoke))
        if kind == "mixtral-smoke":
            rows.extend(upgrade_recovery_rows(kind, model, params,
                                              smoke=smoke))
            rows.extend(mixed_length_serving_rows(kind, model, params,
                                                  smoke=smoke))
            rows.extend(shared_prefix_serving_rows(kind, model, params,
                                                   smoke=smoke))
        seqs = common.eval_token_stream(2 if smoke else 4)
        e = model.cfg.moe.num_experts
        n_entities = model.cfg.num_layers * e
        eng = OffloadEngine(model, params, EngineConfig(
            hi_slots=max(8, n_entities // 3), lo_slots=max(4, n_entities // 6),
            prefetch_p=2))
        # all eval sequences decode as ONE batch through the serving API
        # (union-of-slots expert loading), matching the deployment scenario
        trace = common.collect_trace_batched(eng, seqs)
        d, f = FULL_DIMS[kind]
        cfg = HobbitSimConfig(
            hi_slots=max(8, n_entities // 3), lo_slots=max(4, n_entities // 6),
            hi_bytes=expert_nbytes(d, f, 16), lo_bytes=expert_nbytes(d, f, 4))
        for hw in (RTX4090, JETSON_ORIN):
            res = simulate_systems(trace, eng.num_moe_layers, hw, cfg)
            # beyond-paper: confidence-gated prefetch variant
            from repro.core import OffloadSimulator
            res["hobbit_confgate"] = OffloadSimulator(
                "hobbit", eng.num_moe_layers, hw,
                _dc.replace(cfg, prefetch_conf=0.6)).run(trace)
            base_mo = res["on_demand"]["tok_per_s"]
            base_mi = res["prefetch_lru"]["tok_per_s"]
            base_ll = res["dense_layerwise"]["tok_per_s"]
            hb = res["hobbit"]["tok_per_s"]
            for sysname, r in res.items():
                rows.append((f"fig14_decode_tok_s[{kind}][{hw.name}][{sysname}]",
                             round(r["tok_per_s"], 2), "tok/s (simulated)"))
                rows.append((f"fig14_overlap_fraction[{kind}][{hw.name}][{sysname}]",
                             round(r["overlap_fraction"], 3),
                             "simulated share of transfer hidden by compute"))
            rows.append((f"fig14_speedup_vs_MoE-Offloading[{kind}][{hw.name}]",
                         round(hb / base_mo, 2), "paper: ~3.2x (4090)"))
            rows.append((f"fig14_speedup_vs_MoE-Infinity[{kind}][{hw.name}]",
                         round(hb / base_mi, 2),
                         "paper: 2.30-3.92x (4090), 3.64-9.93x (Orin)"))
            rows.append((f"fig14_speedup_vs_llama.cpp[{kind}][{hw.name}]",
                         round(hb / base_ll, 2), "paper: 13-19x (Orin)"))
            hbc = res["hobbit_confgate"]["tok_per_s"]
            rows.append((f"beyond_confgate_speedup_vs_MO[{kind}][{hw.name}]",
                         round(hbc / base_mo, 2),
                         "beyond-paper: confidence-gated prefetch"))
            rows.append((f"beyond_confgate_vs_paper_hobbit[{kind}][{hw.name}]",
                         round(hbc / hb, 2),
                         "gain over paper-faithful prefetch at 65% pred acc"))
    return rows


if __name__ == "__main__":
    import argparse
    import json
    import pathlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one model, fewer sequences/steps (CI configuration)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write rows as JSON ({rows: {name: value}, "
                         "notes: {name: note}}) — the artifact "
                         "tools/check_bench.py gates against "
                         "benchmarks/baseline.json")
    args = ap.parse_args()
    rows = run(smoke=args.smoke)
    for r in rows:
        print(",".join(map(str, r)))
    if args.json:
        out = pathlib.Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps({
            "schema": 1,
            "smoke": args.smoke,
            "rows": {name: val for name, val, _ in rows},
            "notes": {name: note for name, _, note in rows},
        }, indent=2))
        print(f"wrote {out}")
