"""Steady-state jit recompilation guards over the decode hot path.

The decode loop's performance story (fixed-P pair grids, one-expert
commit scatters, static page tables, donated pools) collapses if any step
retraces: one silent recompile costs more than a hundred steps.  These
tests warm a backend up, snapshot every jitted callable's compiled-variant
count (`_cache_size()`),
run more decode steps at identical shapes, and require the counts to be
bit-identical — for the single-stream, small-page and multi-stream-staged
engine configurations plus the paged dense backend.
"""

import dataclasses

import jax
import numpy as np
import pytest

from conftest import assert_no_recompiles, jit_cache_sizes
from repro.configs import get_config, smoke_variant
from repro.core import EngineConfig, OffloadEngine
from repro.models import build_model
from repro.serving.api import DenseBackend, HobbitBackend
from repro.serving.decode import sample_token

WARMUP, STEADY = 8, 8

ENGINE_CONFIGS = {
    # grouped batched dispatch, single-stream FIFO staging: isolates the
    # grouped decode jits (one gating matmul + hi GEMM + lo dequant-GEMM per
    # layer)
    "grouped": dict(hi_slots=8, lo_slots=4, grouped=True, streams=1,
                    ordered=True),
    # small pages: decode crosses page boundaries through attn_paged over
    # the shared page pool
    "paged": dict(hi_slots=8, lo_slots=4, kv_page_size=4, kv_pages=32),
    # multi-stream byte-budgeted staging riding alongside decode
    "staged": dict(hi_slots=8, lo_slots=4, streams=2),
}


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=2, d_model=64,
                        vocab=128)
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _decode_steps(backend, tok, n):
    for _ in range(n):
        lg = backend.step(tok)
        tok = np.asarray(sample_token(lg, jax.random.PRNGKey(0), 0.0))
    return tok


def _drive(backend, fns):
    """Warm up, snapshot compile counts, decode more, snapshot again."""
    prompts = (np.arange(6, dtype=np.int32).reshape(2, 3) % 100) + 1
    backend.start_batch(2, 24)
    lg = backend.prefill(prompts)
    tok = np.asarray(sample_token(lg, jax.random.PRNGKey(0), 0.0))
    tok = _decode_steps(backend, tok, WARMUP)
    before = jit_cache_sizes(fns())
    _decode_steps(backend, tok, STEADY)
    after = jit_cache_sizes(fns())
    return before, after


@pytest.mark.parametrize("name", sorted(ENGINE_CONFIGS))
def test_engine_decode_steady_state_zero_recompiles(setup, name):
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(**ENGINE_CONFIGS[name]))
    be = HobbitBackend(eng)
    try:
        before, after = _drive(be, lambda: dict(eng._jit_cache))
        assert before and any(v > 0 for v in before.values())
        assert_no_recompiles(before, after)
    finally:
        be.close()


def test_kernel_tier_decode_steady_state_zero_recompiles(setup, monkeypatch):
    """Grouped + paged decode with Pallas dispatch active (REPRO_KERNEL_MODE
    =pallas, interpret on CPU): the kernel tier's tiling/padding choices and
    scalar-prefetch operands (page table, combine rows) must not
    reintroduce per-step recompiles."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    m, params = setup
    eng = OffloadEngine(m, params, EngineConfig(
        hi_slots=8, lo_slots=4, grouped=True, kv_page_size=4, kv_pages=32))
    be = HobbitBackend(eng)
    try:
        before, after = _drive(be, lambda: dict(eng._jit_cache))
        assert before and any(v > 0 for v in before.values())
        assert_no_recompiles(before, after)
    finally:
        be.close()


def test_paged_dense_decode_steady_state_zero_recompiles(setup):
    m, params = setup
    be = DenseBackend(m, params, paged=True, page_size=4, kv_pages=32,
                      prefill_chunk=4)

    def fns():
        return {"step": be._step, "paged_step": be._paged_step,
                "chunk_prefill": be._admission._fn,
                **{("prefill", k): v for k, v in be._prefill_fns.items()}}

    try:
        before, after = _drive(be, fns)
        assert any(v > 0 for v in before.values())
        assert_no_recompiles(before, after)
    finally:
        be.close()
