"""The per-layer metrics that read the program's own spans and counters
(``bench/program_stats.py``): their values on a tiny engine's window, and
None, without raising, for a program that does not report them or a window
without decode steps."""

import dataclasses
import json

import jax
import numpy as np
import pytest

import cell

NEW = ["engine_step_ms", "span_ms.prologue", "span_ms.attn", "span_ms.gate",
       "span_ms.route", "span_ms.prefetch", "span_ms.stage_wait",
       "span_ms.commit", "span_ms.plan", "span_ms.ship_overflow",
       "span_ms.ffn", "span_ms.logits", "step_unattributed_share",
       "overflow_mb_per_step", "commit_mb_per_step", "host_syncs_per_step",
       "served_lo_share"]
STEPS = 3


def ctx_for(stats_open, stats_close, steps):
    calls = [{"kind": "step", "in_win": True}] * steps
    return cell.Ctx(stats_open=stats_open, stats_close=stats_close,
                    calls=calls)


@pytest.fixture(scope="module")
def window():
    """Stats around STEPS decode steps of a tiny engine at batch 2."""
    from repro.configs import get_config, smoke_variant
    from repro.core import EngineConfig
    from repro.models import build_model
    from repro.serving.api import BackendConfig, make_backend
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=2, d_model=64,
                        vocab=128)
    cfg = dataclasses.replace(cfg, dtype="float32")
    m = build_model(cfg)
    be = make_backend(BackendConfig(
        kind="hobbit", paged=True, page_size=4, prefill_chunk=8,
        engine=EngineConfig(hi_slots=4, lo_slots=4)), m,
        m.init(jax.random.PRNGKey(0)))
    be.start_batch(2, 48)
    lg = be.prefill(np.random.default_rng(0).integers(0, 128, (2, 6)))
    before = json.loads(json.dumps(be.stats()))
    for _ in range(STEPS):
        lg = be.step(np.argmax(lg, -1).astype(np.int32))
    after = json.loads(json.dumps(be.stats()))
    eng = be.engine
    yield before, after, eng
    be.close()


def test_every_new_metric_is_declared_for_the_chat_cell():
    bench = json.loads((cell.ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == ["phi-moe.chat"], name
        cell.load_reader(name)


def test_readers_on_a_tiny_engine(window):
    before, after, eng = window
    ctx = ctx_for(before, after, STEPS)
    got = {n: cell.load_reader(n)(ctx) for n in NEW}
    assert all(v is not None for v in got.values()), got
    layers = eng.num_moe_layers
    assert got["host_syncs_per_step"] == 2 + 3 * layers
    itemsize = np.dtype(eng.dtype).itemsize
    ovf = sum(a.size * itemsize if n.startswith("hi_") else a.nbytes
              for n, a in eng._ovf_np.items())
    assert got["overflow_mb_per_step"] == pytest.approx(layers * ovf / 1e6)
    assert 0.0 <= got["step_unattributed_share"] < 100.0
    spans = sum(got[n] for n in NEW if n.startswith("span_ms.")
                and n != "span_ms.commit")
    assert spans <= got["engine_step_ms"] * (1 + 1e-9)
    served = {k: after[f"served_{k}"] - before[f"served_{k}"]
              for k in ("hi", "lo", "skip")}
    assert got["served_lo_share"] == pytest.approx(
        100.0 * (served["lo"] + served["skip"]) / sum(served.values()))


def test_a_program_without_the_counters_reads_none(window):
    before, after, _ = window
    new_keys = ("spans", "overflow_shipped_bytes", "commit_shipped_bytes",
                "host_syncs", "served_hi", "served_lo", "served_skip")
    old = [{k: v for k, v in s.items() if k not in new_keys}
           for s in (before, after)]
    for name in NEW:
        assert cell.load_reader(name)(ctx_for(*old, STEPS)) is None, name


def test_a_window_without_steps_reads_none(window):
    before, after, _ = window
    for name in NEW:
        assert cell.load_reader(name)(ctx_for(before, after, 0)) is None, name
