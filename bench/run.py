#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/``), traffic mix
(``bench/traffic/``) and metrics (``bench/metrics/``) are found by name from
``BENCHMARK.json``.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, the device's busy time and a breakdown
of a profiler trace of the window.  The last line of stdout is one JSON
object; the numbers the output check compared, each with its limit, are
the last lines of stderr and the last key of that object.

Exits non-zero, printing no result, without a TPU, on a device kind that
``bench/peaks.json`` does not list, with fewer chips than the cell asks
for, or without the program next to the benchmark.

``--control 1`` also reads the control (the reference in float8) at the
same positions and holds its numbers to the same limits, for setting them.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def refusal(devices, peaks, chips: int):
    """Why these devices cannot run a cell of ``chips`` chips, or None."""
    dev = devices[0]
    if dev.platform != "tpu":
        return f"no TPU: JAX's first device is {dev.platform!r}"
    if dev.device_kind not in peaks:
        return f"device kind {dev.device_kind!r} is not in bench/peaks.json"
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX found {len(devices)}"
    return None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        fail("--seed must be a non-negative whole number")

    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.exists():
        fail(f"no {bench_file}")
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; cells: {sorted(cells)}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is missing: no {ROOT / 'src' / 'repro'}")

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    why = refusal(devices, json.loads((HERE / "peaks.json").read_text()),
                  int(cells[args.workload]["chips"]))
    if why:
        fail(why)
    dev = devices[0]

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program, however quick to compile, is kept: a run then loads
    # all of them and compiles nothing the first run of the cell compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"{args.workload} seed {args.seed}: {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache}")

    import cell
    out = cell.run_cell(cells[args.workload], args.seed, args.seconds,
                        bool(args.trace), t_start=T_START,
                        control=bool(args.control), bench=bench, log=log)
    compared = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in out.pop("compared")}
    line = {"correct": out.pop("correct"), "attempted": out.pop("attempted"),
            "failed": out.pop("failed"), "metrics": out.pop("metrics"),
            "device": out.pop("device")}
    if "breakdown" in out:
        line["breakdown"] = out.pop("breakdown")
    line.update(out)
    line["compared"] = compared
    log(f"run took {time.perf_counter() - T_START:.3f} s")
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
