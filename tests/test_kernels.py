"""Pallas kernel validation: interpret-mode kernel body vs pure-jnp oracle,
swept over shapes, dtypes and bit widths."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.dequant_matmul import (
    dequant_matmul_pallas,
    grouped_dequant_combine_pallas,
    grouped_dequant_matmul_pallas,
)
from repro.kernels.stacked_gating import gating_topk_pallas, stacked_gating_pallas
from repro.kernels.ops import dequant_matmul, stacked_gating
from repro.quant import quantize


def _mk(m, k, n, bits, group, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    q = quantize(w, bits=bits, group_size=group)
    return x, q


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("m,k,n", [(8, 256, 128), (16, 512, 256), (8, 256, 384)])
def test_dequant_matmul_kernel_vs_oracle(bits, m, k, n):
    x, q = _mk(m, k, n, bits, 128, jnp.float32)
    got = dequant_matmul_pallas(
        x, q.data, q.scale, bits=bits, group_size=128,
        block_m=8, block_n=128, block_k=256, interpret=True)
    want = ref.dequant_matmul_ref(x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bits,group", [(8, 64), (4, 128)])
def test_dequant_matmul_dtypes_and_groups(dtype, bits, group):
    x, q = _mk(8, 256, 128, bits, group, dtype, seed=3)
    got = dequant_matmul_pallas(
        x, q.data, q.scale, bits=bits, group_size=group,
        block_m=8, block_n=128, block_k=256, interpret=True)
    want = ref.dequant_matmul_ref(x, q)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_dequant_matmul_multi_kstep_accumulation():
    """k split across grid steps must accumulate identically."""
    x, q = _mk(8, 1024, 128, 4, 128, jnp.float32, seed=5)
    got = dequant_matmul_pallas(
        x, q.data, q.scale, bits=4, group_size=128,
        block_m=8, block_n=128, block_k=256, interpret=True)
    want = ref.dequant_matmul_ref(x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-3)


def test_ops_wrapper_pads_ragged_shapes():
    """ops.dequant_matmul must handle M/N/K not divisible by blocks (forced pallas)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(5, 384)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(384, 96)), jnp.float32)
    q = quantize(w, bits=8, group_size=128)
    got = dequant_matmul(x, q, mode="pallas")
    want = ref.dequant_matmul_ref(x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_ops_wrapper_leading_batch_dims():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 3, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    q = quantize(w, bits=4, group_size=128)
    got = dequant_matmul(x, q, mode="pallas")
    assert got.shape == (2, 3, 128)
    want = ref.dequant_matmul_ref(x.reshape(-1, 256), q).reshape(2, 3, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("p,b,d,e", [(1, 1, 256, 8), (4, 2, 512, 16), (3, 8, 1024, 64)])
def test_stacked_gating_kernel_vs_oracle(p, b, d, e):
    rng = np.random.default_rng(p * 100 + e)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(p, d, e)), jnp.float32)
    got = stacked_gating_pallas(x, g, block_d=256, interpret=True)
    want = ref.stacked_gating_ref(x, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_stacked_gating_bf16_inputs():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 512)), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=(2, 512, 8)), jnp.bfloat16)
    got = stacked_gating_pallas(x, g, block_d=512, interpret=True)
    want = ref.stacked_gating_ref(x, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-2, atol=3e-2)


def test_stacked_gating_wrapper_pads_d():
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(2, 384)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(3, 384, 8)), jnp.float32)
    got = stacked_gating(x, g, mode="pallas", block_d=256)
    want = ref.stacked_gating_ref(x, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_auto_mode_cpu_uses_oracle_path(monkeypatch):
    """On CPU 'auto' must route to the XLA dense path (fast) and agree."""
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    x, q = _mk(4, 256, 128, 8, 128, jnp.float32, seed=17)
    ops.reset_dispatch_counts()
    got = dequant_matmul(x, q, mode="auto")
    want = ref.dequant_matmul_ref(x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    assert ops.dispatch_counts() == {"dequant_matmul.xla": 1}


def test_env_override_routes_auto_to_pallas_on_cpu(monkeypatch):
    """REPRO_KERNEL_MODE=pallas flips 'auto' to the interpret-mode kernel
    (the CI parity job's dispatch), and the counter records the flip."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    x, q = _mk(4, 256, 128, 8, 128, jnp.float32, seed=19)
    ops.reset_dispatch_counts()
    got = dequant_matmul(x, q, mode="auto")
    want = ref.dequant_matmul_ref(x, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert ops.dispatch_counts() == {"dequant_matmul.pallas_interpret": 1}


# ----------------------------------------------------------- flash decode
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.ops import flash_decode


@pytest.mark.parametrize("b,s,h,hd,bs", [(2, 512, 4, 64, 128),
                                          (1, 256, 2, 128, 256),
                                          (3, 1024, 8, 64, 256)])
def test_flash_decode_kernel_vs_oracle(b, s, h, hd, bs):
    rng = np.random.default_rng(b * 100 + s)
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
    got = flash_decode_pallas(q, k, v, lengths, block_s=bs, interpret=True)
    want = ref.flash_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_dtypes(dtype):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(2, 4, 64)), dtype)
    k = jnp.asarray(rng.normal(size=(2, 512, 4, 64)), dtype)
    v = jnp.asarray(rng.normal(size=(2, 512, 4, 64)), dtype)
    lengths = jnp.asarray([100, 512], jnp.int32)
    got = flash_decode_pallas(q, k, v, lengths, block_s=128, interpret=True)
    want = ref.flash_decode_ref(q, k, v, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_flash_decode_wrapper_gqa_and_ragged():
    """Wrapper expands kv heads and pads ragged cache length (forced pallas)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 8, 64)), jnp.float32)   # hq=8
    k = jnp.asarray(rng.normal(size=(2, 300, 2, 64)), jnp.float32)  # hkv=2, S=300
    v = jnp.asarray(rng.normal(size=(2, 300, 2, 64)), jnp.float32)
    lengths = jnp.asarray([300, 17], jnp.int32)
    got = flash_decode(q, k, v, lengths, mode="pallas", block_s=128)
    kx = jnp.repeat(k, 4, axis=2)
    vx = jnp.repeat(v, 4, axis=2)
    want = ref.flash_decode_ref(q, kx, vx, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_length_zero_block_safe():
    """Blocks fully beyond `length` contribute nothing (numerically stable)."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    lengths = jnp.asarray([1], jnp.int32)
    got = flash_decode_pallas(q, k, v, lengths, block_s=128, interpret=True)
    want = ref.flash_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


# ----------------------------------------------------- paged flash decode
from repro.kernels.flash_decode import paged_flash_decode_pallas


def _mk_paged(b, hq, hkv, hd, psz, maxp, num_pages, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, hq, hd)), dtype)
    pk = jnp.asarray(rng.normal(size=(num_pages, psz, hkv, hd)), dtype)
    pv = jnp.asarray(rng.normal(size=(num_pages, psz, hkv, hd)), dtype)
    table = jnp.asarray(rng.integers(0, num_pages, (b, maxp)), jnp.int32)
    return q, pk, pv, table


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("psz,maxp", [(4, 6), (8, 3)])
def test_paged_flash_decode_kernel_vs_oracle(hq, hkv, psz, maxp):
    """Table-driven kernel == gather + masked-softmax oracle, incl. GQA
    (query heads grouped per kv head in-kernel, K/V never repeated)."""
    q, pk, pv, table = _mk_paged(3, hq, hkv, 32, psz, maxp, 12,
                                 seed=hq * 10 + psz)
    rng = np.random.default_rng(1)
    lengths = jnp.asarray(rng.integers(1, psz * maxp + 1, (3,)), jnp.int32)
    got = paged_flash_decode_pallas(q, pk, pv, table, lengths, interpret=True)
    want = ref.paged_flash_decode_ref(q, pk, pv, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lengths", [[0, 0], [1, 0], [4, 8], [12, 1]])
def test_paged_flash_decode_edge_lengths(lengths):
    """Length 0 (released slot) returns exact zeros; lengths exactly on a
    page boundary and single-token sequences match the oracle."""
    q, pk, pv, table = _mk_paged(2, 4, 2, 16, 4, 3, 8, seed=3)
    ln = jnp.asarray(lengths, jnp.int32)
    got = paged_flash_decode_pallas(q, pk, pv, table, ln, interpret=True)
    want = ref.paged_flash_decode_ref(q, pk, pv, table, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()
    for r, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(np.asarray(got[r]), 0.0)


def test_paged_flash_decode_junk_table_rows_isolated():
    """An inactive slot's page-table row may point at pages now owned by a
    neighbour: its garbage must stay confined to its own output row."""
    q, pk, pv, table = _mk_paged(3, 4, 2, 16, 4, 3, 8, seed=5)
    ln = jnp.asarray([7, 12, 3], jnp.int32)
    base = paged_flash_decode_pallas(q, pk, pv, table, ln, interpret=True)
    # rewrite row 1's table to junk (all pages alias a neighbour's)
    junk = table.at[1].set(table[0, 0])
    got = paged_flash_decode_pallas(q, pk, pv, junk, ln, interpret=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(base[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(base[2]),
                               rtol=1e-6, atol=1e-6)
    want = ref.paged_flash_decode_ref(q, pk, pv, junk, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode_dtypes(dtype):
    q, pk, pv, table = _mk_paged(2, 4, 2, 32, 4, 4, 8, dtype=dtype, seed=7)
    ln = jnp.asarray([5, 16], jnp.int32)
    got = paged_flash_decode_pallas(q, pk, pv, table, ln, interpret=True)
    want = ref.paged_flash_decode_ref(q, pk, pv, table, ln)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_paged_flash_decode_softcap():
    """Logit softcap applies BEFORE masking, matching layers.mha's order."""
    q, pk, pv, table = _mk_paged(2, 4, 4, 16, 4, 3, 8, seed=9)
    ln = jnp.asarray([5, 11], jnp.int32)
    got = paged_flash_decode_pallas(q, pk, pv, table, ln, interpret=True,
                                    softcap=5.0)
    want = ref.paged_flash_decode_ref(q, pk, pv, table, ln, softcap=5.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    uncapped = paged_flash_decode_pallas(q, pk, pv, table, ln, interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(uncapped)).max() > 1e-6


# ---------------------------------------- grouped dequant GEMM + combine
def _mk_grouped(p, k, n, bits, group, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(p, k)), jnp.float32)
    data, scale = [], []
    for i in range(p):
        qt = quantize(jnp.asarray(rng.normal(size=(k, n)), jnp.float32),
                      bits=bits, group_size=group)
        data.append(qt.data)
        scale.append(qt.scale)
    return x, jnp.stack(data), jnp.stack(scale)


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_grouped_dequant_matmul_single_launch_vs_oracle(bits):
    """The (P, K/bk)-grid kernel == dense dequantize + einsum oracle."""
    x, data, scale = _mk_grouped(6, 256, 64, bits, 64, seed=bits)
    got = grouped_dequant_matmul_pallas(x, data, scale, bits=bits,
                                        group_size=64, block_k=128,
                                        interpret=True)
    want = ops.grouped_dequant_matmul(x, data, scale, bits=bits,
                                      group_size=64, mode="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("block_k", [64, 128, 256])
def test_grouped_dequant_combine_vs_oracle(block_k):
    """Fused GEMM + gated combine-scatter == einsum + .at[].add oracle,
    across k-step counts (accumulation over both kk and same-row pairs)."""
    b, k_, n = 4, 256, 64
    x, data, scale = _mk_grouped(8, k_, n, 4, 64, seed=block_k)
    rows = jnp.asarray([0, 0, 1, 1, 2, 2, 3, 3], jnp.int32)
    wts = jnp.asarray(np.random.default_rng(1).uniform(0.1, 1.0, 8),
                      jnp.float32)
    got = grouped_dequant_combine_pallas(x, data, scale, rows, wts, bits=4,
                                         group_size=64, num_rows=b,
                                         block_k=block_k, interpret=True)
    want = ref.grouped_dequant_combine_ref(x, data, scale, rows, wts, bits=4,
                                           group_size=64, num_rows=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_grouped_dequant_combine_pads_and_unvisited_rows():
    """Pad pairs (row == num_rows, weight 0) are dropped in-kernel; rows no
    real pair visits come back as exact zeros, never NaN garbage."""
    b = 5
    x, data, scale = _mk_grouped(6, 128, 32, 4, 32, seed=11)
    # rows 0 and 2 visited (twice / once), rows 1/3/4 unvisited; pads at end
    rows = jnp.asarray([0, 0, 2, b, b, b], jnp.int32)
    wts = jnp.asarray([0.7, 0.3, 1.0, 0.0, 0.0, 0.0], jnp.float32)
    got = grouped_dequant_combine_pallas(x, data, scale, rows, wts, bits=4,
                                         group_size=32, num_rows=b,
                                         block_k=64, interpret=True)
    want = ref.grouped_dequant_combine_ref(x, data, scale, rows, wts, bits=4,
                                           group_size=32, num_rows=b)
    assert np.isfinite(np.asarray(got)).all()
    for r in (1, 3, 4):
        np.testing.assert_array_equal(np.asarray(got[r]), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("n", [640, 1024])
@pytest.mark.parametrize("kernel", ["matmul", "combine"])
def test_grouped_dequant_n_blocks_vs_oracle(kernel, n, bits):
    """Real-width tiling: N wider than one 512-lane block (640 -> five
    128-lane blocks, 1024 -> two 512-lane blocks) and two k-blocks whose
    packed width is a multiple of 128, so the N-block index maps, the
    combine's revisits within each N block and its re-initialisation
    when the N block changes all run; the combine also carries pads and
    an unvisited row."""
    b, k_ = 4, 1024
    x, data, scale = _mk_grouped(6, k_, n, bits, 64, seed=n + bits)
    kw = dict(bits=bits, group_size=64)
    if kernel == "matmul":
        got = grouped_dequant_matmul_pallas(x, data, scale, block_k=512,
                                            interpret=True, **kw)
        want = ops.grouped_dequant_matmul(x, data, scale, mode="xla", **kw)
    else:
        # rows 0, 1 and 3 visited, row 2 unvisited; two pads at the end
        rows = jnp.asarray([0, 0, 1, 3, b, b], jnp.int32)
        wts = jnp.asarray([0.7, 0.3, 1.0, 0.5, 0.0, 0.0], jnp.float32)
        got = grouped_dequant_combine_pallas(x, data, scale, rows, wts,
                                             num_rows=b, block_k=512,
                                             interpret=True, **kw)
        want = ref.grouped_dequant_combine_ref(x, data, scale, rows, wts,
                                               num_rows=b, **kw)
        np.testing.assert_array_equal(np.asarray(got[2]), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_grouped_dequant_combine_ops_wrapper_matches_ref():
    """ops-level dispatch: forced pallas == forced xla on the same inputs."""
    b = 3
    x, data, scale = _mk_grouped(4, 128, 48, 8, 64, seed=13)
    rows = jnp.asarray([0, 1, 1, b], jnp.int32)
    wts = jnp.asarray([1.0, 0.4, 0.6, 0.0], jnp.float32)
    kw = dict(bits=8, group_size=64, num_rows=b)
    got = ops.grouped_dequant_combine(x, data, scale, rows, wts,
                                      mode="pallas", **kw)
    want = ops.grouped_dequant_combine(x, data, scale, rows, wts,
                                       mode="xla", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ----------------------------------------------------------- gating top-k
@pytest.mark.parametrize("p,b,d,e,k", [(1, 2, 256, 8, 2), (3, 4, 512, 16, 4),
                                       (2, 1, 128, 8, 1)])
def test_gating_topk_kernel_vs_oracle(p, b, d, e, k):
    """Fused matmul+softmax+top-k == einsum + jax.nn.softmax + lax.top_k,
    including across multiple D blocks (selection runs on the last k-step)."""
    rng = np.random.default_rng(p * 10 + e)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(p, d, e)), jnp.float32)
    got_l, got_v, got_i = gating_topk_pallas(x, g, top_k=k, block_d=128,
                                             interpret=True)
    want_l, want_v, want_i = ref.gating_topk_ref(x, g, top_k=k)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_gating_topk_ties_select_lowest_index():
    """Exactly tied logits pick the lowest expert index on both paths."""
    x = jnp.ones((2, 4), jnp.float32)
    g = jnp.zeros((1, 4, 6), jnp.float32)          # all logits identical
    _, v_p, i_p = gating_topk_pallas(x, g, top_k=3, interpret=True)
    _, v_r, i_r = ref.gating_topk_ref(x, g, top_k=3)
    np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(i_p[0, 0]), [0, 1, 2])
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_r), rtol=1e-6)


def test_gating_topk_ops_wrapper_pads_d():
    """ops.gating_topk pads ragged D; selected experts and probs agree."""
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(3, 96)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, 96, 8)), jnp.float32)
    _, v_p, i_p = ops.gating_topk(x, g, top_k=2, mode="pallas", block_d=64)
    _, v_r, i_r = ops.gating_topk(x, g, top_k=2, mode="xla")
    np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_r),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- dispatch observability
def test_dispatch_counters_record_every_op(monkeypatch):
    """Each public op records the implementation that ran, keyed
    "<op>.<impl>" — the engine surfaces these via stats()["kernel_dispatch"]
    so an auto fallback is never silent."""
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    ops.reset_dispatch_counts()
    x, data, scale = _mk_grouped(2, 128, 32, 4, 32, seed=23)
    ops.grouped_dequant_matmul(x, data, scale, bits=4, group_size=32,
                               mode="auto")
    ops.grouped_dequant_matmul(x, data, scale, bits=4, group_size=32,
                               mode="pallas")
    rows = jnp.asarray([0, 1], jnp.int32)
    wts = jnp.ones((2,), jnp.float32)
    ops.grouped_dequant_combine(x, data, scale, rows, wts, bits=4,
                                group_size=32, num_rows=2, mode="auto")
    rng = np.random.default_rng(0)
    xg = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    gg = jnp.asarray(rng.normal(size=(1, 64, 8)), jnp.float32)
    ops.gating_topk(xg, gg, top_k=2, mode="auto")
    q, pk, pv, table = _mk_paged(2, 2, 2, 16, 4, 2, 4, seed=29)
    ops.paged_flash_decode(q, pk, pv, table, jnp.asarray([3, 5], jnp.int32),
                           mode="auto")
    c = ops.dispatch_counts()
    assert c["grouped_dequant_matmul.xla"] == 1
    assert c["grouped_dequant_matmul.pallas_interpret"] == 1
    assert c["grouped_dequant_combine.xla"] == 1
    assert c["gating_topk.xla"] == 1
    assert c["paged_flash_decode.xla"] == 1
