"""The harness's control flow, at tiny widths on the CPU with the Pallas
kernels in interpret mode: the closed loop refills, the window stops the
run, attempted and failed are counted, the output check passes a sound run
and fails an altered token, ``lo_excess`` reads more experts skipped, and
no result is printed without a TPU."""

import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

import run as run_py
from conftest import run_tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**33 + 5


def test_prompt_cell_refills_and_stops():
    out = run_tiny("prompt", SEED)
    clients = 4
    assert out["counts"]["prefill_calls"] > 0
    # every request that finished made its client send the next one
    assert out["attempted"] > clients
    assert set(out["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert out["failed"] == 0
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("kind", ["chat", "single-user"])
def test_decode_cell_window_abandons_in_flight(kind):
    out = run_tiny(kind, SEED)
    assert out["counts"]["decode_steps"] > 0
    assert set(out["metrics"]) == {"decode_tok_s", "itl_p95_ms", "setup_s"}
    # outputs of 64-256 tokens: requests are still decoding when the
    # window closes; they are abandoned, not failed
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert {r["name"] for r in out["compared"]} == {"token_gap", "route_gap"}
    assert out["check"]["lo_excess"] is not None
    assert out["correct"], out["compared"]


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the decode step's logits put
    row 0's best on the next vocabulary id."""
    from repro.serving.api import HobbitBackend
    orig = HobbitBackend.step

    def step(self, tokens):
        lg = np.array(orig(self, tokens))
        lg[0] = np.roll(lg[0], 1)
        return lg

    monkeypatch.setattr(HobbitBackend, "step", step)
    out = run_tiny("chat", SEED)
    assert out["counts"]["decode_steps"] > 0
    assert not out["correct"], out["compared"]


def test_more_skipped_reads_lo_excess(monkeypatch):
    """The program run with thresholds that skip every expert after rank 0
    serves below HOBBIT's rule under the configuration's thresholds, and
    ``lo_excess`` reads it."""
    import cell
    from repro.core.scoring import Thresholds
    orig = cell.make_server

    def make_server(*a, **kw):
        srv, proxy = orig(*a, **kw)
        proxy.engine.loader.th = Thresholds(0.0, 0.0)
        return srv, proxy

    sound = run_tiny("chat", SEED)["check"]["lo_excess"]
    monkeypatch.setattr(cell, "make_server", make_server)
    out = run_tiny("chat", SEED)
    assert out["check"]["lo_excess"] >= sound + 20.0, (sound, out["check"])


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "phi-moe.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_unknown_kind_and_too_few_chips():
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert run_py.refusal([tpu], peaks, 1) is None
    assert "peaks" in run_py.refusal(
        [types.SimpleNamespace(platform="tpu", device_kind="TPU v9")], peaks, 1)
    assert "4 chips" in run_py.refusal([tpu], peaks, 4)
