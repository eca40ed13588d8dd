import os
# 512 placeholder devices for the production mesh; LICM disabled because XLA
# otherwise hoists an fp32 convert of the whole remat residual stack out of
# the backward loop (a +5 GB/chip copy at DeepSeek scale).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
    + " --xla_disable_hlo_passes=while-loop-invariant-code-motion")

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production mesh with 512 placeholder host devices; print memory_analysis,
cost_analysis and parsed collective bytes; emit a JSON record per run.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results.jsonl
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, ASSIGNED_ARCHS, INPUT_SHAPES, get_config, shape_supported
from repro.launch.mesh import make_production_mesh, num_chips
from repro.launch.roofline import Roofline, model_flops


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            verbose: bool = True, expert_bits: int = 0) -> dict:
    import dataclasses as _dc

    from repro.launch.specs import input_specs

    cfg = get_config(arch)
    if expert_bits and cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(
            cfg.moe, expert_precision=f"int{expert_bits}"))
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "multi_pod": multi_pod, "status": "skip"}
    if not shape_supported(arch, shape_name):
        rec["reason"] = "long-context skip (DESIGN.md §5)"
        return rec
    t0 = time.time()
    try:
        step_fn, args, in_sh, donate = input_specs(cfg, shape_name, mesh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step_fn, in_shardings=in_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        # trip-count-aware analysis (XLA's cost_analysis counts while bodies
        # once, under-reporting scanned layer stacks; see hlo_analysis.py)
        from repro.launch.hlo_analysis import analyze
        ana = analyze(hlo)
        coll = ana["collectives"]
        shape = INPUT_SHAPES[shape_name]
        n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "flops_per_chip": float(ana["flops"]),
            "bytes_per_chip": float(ana["bytes"]),
            "xla_cost_analysis": {"flops": float(cost.get("flops", -1.0)),
                                  "bytes": float(cost.get("bytes accessed", -1.0))},
            "collectives": coll,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_bytes_per_chip": mem.argument_size_in_bytes
                    + mem.temp_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes,
            },
            "n_tokens": n_tokens,
            "model_flops": model_flops(cfg, shape, n_tokens),
            "chips": num_chips(mesh),
        })
        rl = Roofline(arch, shape_name, mesh_name,
                      rec["flops_per_chip"], rec["bytes_per_chip"],
                      coll["total"])
        rec["roofline"] = rl.asdict()
        if verbose:
            print(f"[{arch} x {shape_name} @ {mesh_name}] OK "
                  f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
            print(f"  memory_analysis: {json.dumps(rec['memory'])}")
            print(f"  cost_analysis: flops/chip={rec['flops_per_chip']:.3e} "
                  f"bytes/chip={rec['bytes_per_chip']:.3e}")
            print(f"  collectives: {json.dumps(coll)}")
            print(f"  roofline: compute={rl.compute_s:.4e}s memory={rl.memory_s:.4e}s "
                  f"collective={rl.collective_s:.4e}s -> {rl.bottleneck}-bound")
    except Exception as e:  # noqa
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
        if verbose:
            print(f"[{arch} x {shape_name} @ {mesh_name}] FAIL {rec['error']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape) pair")
    ap.add_argument("--include-paper-archs", action="store_true",
                    help="also dry-run mixtral-8x7b / phi-moe")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--expert-bits", type=int, default=0, choices=[0, 4, 8],
                    help="beyond-paper: quantized resident experts (decode)")
    ap.add_argument("--out", type=str, default=None, help="append JSONL here")
    args = ap.parse_args()

    if args.all:
        archs = list(ASSIGNED_ARCHS)
        if args.include_paper_archs:
            archs += [a for a in ARCHS if a not in archs]
        pairs = [(a, s) for a in archs for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        pairs = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape in pairs:
        rec = run_one(arch, shape, multi_pod=args.multi_pod,
                      expert_bits=args.expert_bits)
        if args.expert_bits:
            rec["expert_bits"] = args.expert_bits
        n_fail += rec["status"] == "fail"
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
