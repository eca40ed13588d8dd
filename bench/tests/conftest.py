"""Shared set-up for the benchmark's own tests (``python -m pytest
bench/tests``): the program and the benchmark on the path, and a cell of one
of the benchmark's configurations and traffic mixes cut to a tiny width
that the CPU runs in seconds."""

import copy
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# (configuration, traffic mix) of each kind of cell the tests drive
KINDS = {"chat": ("phi-moe-2l", "chat-2"),
         "single-user": ("mixtral-8x7b-1l", "single-user"),
         "prompt": ("phi-moe-2l", "prompt-only")}
# the end-to-end metrics each kind reports
E2E = {"chat": ["decode_tok_s", "itl_p95_ms", "setup_s"],
       "single-user": ["decode_tok_s", "itl_p95_ms", "setup_s"],
       "prompt": ["ttft_p95_ms", "setup_s"]}
# the decode cell's limits hold for every kind: the numbers mean the same
LIMITS = "phi-moe.chat"


def tiny(kind: str):
    """(cell, bench, configuration, mix) for ``kind`` at tiny widths and
    short prompts; everything else as the benchmark has it."""
    import cell
    import traffic
    cfg_name, mix_name = KINDS[kind]
    cfg = copy.deepcopy(cell.load_config(cfg_name))
    cfg["model"].update(d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
                        d_ff=256, vocab_size=512)
    cfg["model"]["moe"].update(num_experts=4, d_ff_expert=256)
    cfg["engine"].update(hi_slots=2, lo_slots=2)
    mix = copy.deepcopy(traffic.load_mix(mix_name))
    mix["prompt"].update(min=min(mix["prompt"]["min"], 32),
                         median=min(mix["prompt"]["median"], 96),
                         max=min(mix["prompt"]["max"], 200))
    name = f"test.{kind}"
    spec = {"name": name, "config": cfg_name, "traffic": mix_name, "chips": 1}
    bench = {"workloads": [spec], "per_layer": [],
             "end_to_end": [{"name": m, "unit": "-", "workloads": [name]}
                            for m in E2E[kind]]}
    return spec, bench, cfg, mix


def run_tiny(kind: str, seed: int, seconds: float = 3.0, **kw):
    """One run of a tiny cell of ``kind``, held to the decode cell's limits."""
    import cell
    import check
    spec, bench, cfg, mix = tiny(kind)
    return cell.run_cell(spec, seed, seconds, False,
                         t_start=time.perf_counter(), bench=bench, cfgfile=cfg,
                         mix=mix, limits=check.load_limits(LIMITS),
                         log=lambda m: None, **kw)


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    """The Pallas kernels run in interpret mode on the CPU."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
