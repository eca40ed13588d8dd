"""Serving launcher over the unified `InferenceBackend` API.

Both the resident dense path and the HOBBIT mixed-precision offload engine
sit behind the same protocol, so one launcher drives either — single-shot
generation or a continuous-batching request workload:

  # dense, one batched generate call
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
      --backend dense --prompt-len 16 --new-tokens 32

  # HOBBIT offload + simulated edge-hardware latency report
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
      --backend hobbit --prompt-len 16 --new-tokens 32

  # continuous batching: mixed-length requests through the scheduler
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke \
      --backend hobbit --serve-requests 6 --max-batch 2
"""

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_variant
from repro.core import EngineConfig, OffloadEngine, Thresholds
from repro.core.simulator import HARDWARE, HobbitSimConfig, simulate_systems
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.quant.quantize import expert_nbytes
from repro.serving.api import BackendConfig, generate, make_backend
from repro.serving.batching import BatchingServer, Request
from repro.serving.workload import DEFAULT_AGING_S
from repro.training import checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser(
        description="Serve a MoE model through the unified InferenceBackend "
                    "API: --backend dense keeps all weights resident; "
                    "--backend hobbit decodes through the mixed-precision "
                    "expert-offloading engine.  Either backend runs "
                    "single-shot generation or, with --serve-requests, a "
                    "continuous-batching workload through the same "
                    "scheduler.")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", choices=["dense", "hobbit"], default=None,
                    help="inference backend behind the serving API "
                         "(default: dense)")
    ap.add_argument("--mode", choices=["resident", "hobbit"], default=None,
                    help="DEPRECATED alias for --backend "
                         "(resident -> dense)")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=1,
                    help="batch size for single-shot generation "
                         "(both backends support batch >= 1)")
    ap.add_argument("--serve-requests", type=int, default=0,
                    help="if > 0, run N mixed-length requests through the "
                         "continuous-batching scheduler instead of one "
                         "generate call")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="scheduler slots for --serve-requests")
    ap.add_argument("--jit", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="jit the dense prefill/decode steps "
                         "(--no-jit: eager, for debugging)")
    ap.add_argument("--paged-kv", action="store_true",
                    help="paged KV cache for --backend dense: slots draw "
                         "pages from a shared pool instead of each "
                         "allocating max_len up front (all-'attn' archs); "
                         "--backend hobbit always pages, and this flag "
                         "hands it the page flags below")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (--paged-kv)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="KV pool size in pages (--paged-kv; default: the "
                         "dense equivalent, batch * ceil(max_len/page))")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="tokens per chunked-prefill call (--paged-kv)")
    ap.add_argument("--prefix-sharing", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix prefix cache over the KV page pool "
                         "(--paged-kv): admissions alias already-resident "
                         "prompt-prefix pages across slots (copy-on-write "
                         "on divergence) and only pay for their unshared "
                         "suffix.  --no-prefix-sharing prefills every "
                         "prompt in full")
    ap.add_argument("--admit-k", type=int, default=4,
                    help="max requests prefilling concurrently in the "
                         "scheduler (--serve-requests)")
    ap.add_argument("--sched", choices=["slo", "fifo"], default="slo",
                    help="scheduler admission policy (--serve-requests): "
                         "'slo' orders the queue by SLO urgency (priority + "
                         "aging + TTFT slack) and preempts a low-priority "
                         "decode when a more urgent request cannot fit; "
                         "'fifo' is strict arrival order, no preemption")
    ap.add_argument("--aging-s", type=float, default=DEFAULT_AGING_S,
                    help="seconds of queue wait worth one priority level "
                         "(--sched slo): bounds every request's wait, so "
                         "low-priority work cannot starve")
    ap.add_argument("--preempt-margin", type=float, default=1.0,
                    help="effective-priority gap the queued request must "
                         "hold over the best victim before the scheduler "
                         "pauses it (--sched slo); higher = rarer "
                         "preemption")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="mark every Nth --serve-requests request "
                         "priority 2 with a 2 s TTFT SLO (0 = all "
                         "priority 0, no SLOs) — exercises the SLO-aware "
                         "scheduler end to end")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common N-token prefix (a shared system "
                         "prompt) to every --serve-requests prompt, so the "
                         "--prefix-sharing radix cache has something to "
                         "alias (0 = fully distinct prompts)")
    ap.add_argument("--hi-slots", type=int, default=16)
    ap.add_argument("--lo-slots", type=int, default=8)
    ap.add_argument("--t1", type=float, default=0.6)
    ap.add_argument("--t2", type=float, default=0.9)
    ap.add_argument("--streams", type=int, default=2,
                    help="expert staging streams sharing the modeled H2D "
                         "link (hobbit backend; default one hi- + one "
                         "lo-precision stream)")
    ap.add_argument("--ordered", action="store_true",
                    help="FIFO staging issue (with --streams 1 this is the "
                         "PR-2 parity scheduler; default is byte-budgeted "
                         "biggest-gate-first issue with hi->lo downgrades "
                         "under link pressure)")
    ap.add_argument("--upgrade", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="idle-link upgrade pass: re-issue hi copies for "
                         "downgraded (lo-substituted) experts when the hi "
                         "stream has leftover link budget, so downgrades "
                         "stay temporary.  --no-upgrade restores the PR-4 "
                         "per-token downgrade semantics")
    ap.add_argument("--link-gbps", type=float, default=None,
                    help="modeled H2D link bandwidth in GB/s; default "
                         "measures the backend's host->device copy rate at "
                         "startup.  An explicit value also *emulates* the "
                         "link (copies occupy their stream for bytes/link "
                         "seconds) so contended-link behavior is "
                         "reproducible on any backend")
    ap.add_argument("--hw", choices=list(HARDWARE), default="rtx4090",
                    help="hardware cost model for the simulated latency report")
    args = ap.parse_args()
    enable_compile_cache()

    kind = args.backend or {"resident": "dense", "hobbit": "hobbit",
                            None: "dense"}[args.mode]

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
        cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        params, _ = ckpt.restore(args.ckpt_dir, params)

    if kind == "hobbit":
        assert cfg.moe is not None, "--backend hobbit requires a MoE arch"
    # flags mirror BackendConfig 1:1 (the deprecated kwarg form is gone here)
    backend = make_backend(BackendConfig(
        kind=kind, jit=args.jit, paged=args.paged_kv,
        page_size=args.page_size, kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        prefix_sharing=args.prefix_sharing,
        engine=EngineConfig(
            hi_slots=args.hi_slots, lo_slots=args.lo_slots,
            thresholds=Thresholds(args.t1, args.t2),
            streams=args.streams, ordered=args.ordered,
            upgrade=args.upgrade, link_gbps=args.link_gbps)
        if kind == "hobbit" else None), model, params)

    rng = np.random.default_rng(0)
    report = {"backend": kind, "paged_kv": args.paged_kv or kind == "hobbit"}

    if args.serve_requests > 0:
        srv = BatchingServer(backend, max_batch=args.max_batch,
                             max_len=(args.shared_prefix + args.prompt_len * 2
                                      + args.new_tokens + 8),
                             admit_k=args.admit_k, policy=args.sched,
                             aging_s=args.aging_s,
                             preempt_margin=args.preempt_margin)
        common = rng.integers(0, cfg.vocab_size, args.shared_prefix)
        for i in range(args.serve_requests):
            plen = args.prompt_len * (1 + i % 2)
            prompt = np.concatenate(
                [common, rng.integers(0, cfg.vocab_size, plen)])
            urgent = args.priority_every and i % args.priority_every == 0
            srv.submit(Request(
                rid=i, prompt=prompt,
                max_new_tokens=args.new_tokens // (1 + i % 2),
                priority=2 if urgent else 0,
                ttft_slo_s=2.0 if urgent else None))
        srv.run()
        report["serving"] = srv.stats()
        report["scheduler"] = {"policy": args.sched,
                               "preemptions": srv.preemptions}
    else:
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
            jnp.int32)
        res = generate(backend, prompts, args.new_tokens)
        report.update({"prefill_s": res.prefill_s, "decode_s": res.decode_s,
                       "decode_tok_s": res.decode_tok_s,
                       "tokens": res.tokens[0, -8:].tolist()})

    if kind == "hobbit":
        eng: OffloadEngine = backend.engine
        stats = eng.stats()
        hw = HARDWARE[args.hw]
        base = get_config(args.arch)  # full-scale dims for the latency model
        sim_cfg = HobbitSimConfig(
            thresholds=Thresholds(args.t1, args.t2),
            hi_slots=args.hi_slots, lo_slots=args.lo_slots,
            hi_bytes=expert_nbytes(base.d_model, base.moe.d_ff_expert, 16),
            lo_bytes=expert_nbytes(base.d_model, base.moe.d_ff_expert, 4))
        sim = simulate_systems(eng.trace, eng.num_moe_layers, hw, sim_cfg)
        report.update({
            "cache_hit_ratio": round(stats["cache"]["hit_ratio"], 3),
            "loads": {"hi": stats["loads_hi"], "lo": stats["loads_lo"],
                      "skips": stats["skips"]},
            "pred_accuracy": stats["pred_accuracy"],
            # wall-clock loading observability (engine.stats() contract)
            "load_stall_s": round(stats["load_stall_s"], 4),
            "overlap_fraction": round(stats["overlap_fraction"], 3),
            "gating_s": round(stats["gating_s"], 4),
            # multi-stream staging (StagingEngine; docs/METRICS.md)
            "streams": stats["streams"],
            "per_stream_bytes": stats["per_stream_bytes"],
            "issue_reorders": stats["issue_reorders"],
            "precision_downgrades": stats["precision_downgrades"],
            # idle-link upgrade pass: downgrade recovery + residual exposure
            "upgrades": stats["upgrades"],
            "upgrade_bytes": stats["upgrade_bytes"],
            "served_lo_expert_steps": stats["served_lo_expert_steps"],
            "link_utilization": round(stats["link_utilization"], 3),
            "simulated_decode_tok_s": {k: round(v["tok_per_s"], 2)
                                       for k, v in sim.items()},
            "simulated_overlap_fraction": {k: round(v["overlap_fraction"], 3)
                                           for k, v in sim.items()},
            "hw_profile": hw.name,
        })
    backend.close()         # release staging threads before reporting
    print(json.dumps(report))


if __name__ == "__main__":
    main()
