"""Fused groupwise-dequant x matmul Pallas TPU kernel.

This is HOBBIT's expert-compute hot-spot, adapted to TPU: instead of
dequantizing a low-precision expert to bf16 in HBM and then running a GEMM
(the GPU flow: cudaMemcpy + dequant pass + cuBLAS), the packed int8/int4/int2
codes stay packed in HBM and are expanded to fp32 *inside the matmul tile
loop, in VREGs*, after the DMA into VMEM.  Decode-time expert FFNs are memory
bound, so shrinking the bytes the MXU pipeline pulls from HBM by 2-8x moves
the memory-roofline term directly.

Layout contract (matches repro.quant.quantize):
    x      (M, K)            bf16/f32 activations
    data   (K // pack, N)    int8, `pack` codes per byte along K
    scale  (K // group, N)   f32 groupwise scales
    out    (M, N)            f32

Tiling: grid (M/bm, N/bn, K/bk) with out-block accumulation over the k axis.
``bk`` must be a multiple of the quantization group size so each k-step sees
an integer number of scale rows; block shapes are MXU-aligned (128 lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.quantize import PACK_FACTOR


def _unpack_block(data_blk, bits: int):
    """(bk//pack, bn) int8 -> (bk, bn) f32 signed codes, inside the kernel."""
    pack = PACK_FACTOR[bits]
    if pack == 1:
        return data_blk.astype(jnp.float32)
    u = data_blk.astype(jnp.int32) & 0xFF  # treat as unsigned byte lanes
    mask = (1 << bits) - 1
    half = 1 << (bits - 1)
    parts = []
    for i in range(pack):
        nib = (u >> (bits * i)) & mask
        parts.append(jnp.where(nib >= half, nib - (1 << bits), nib))
    codes = jnp.stack(parts, axis=1)  # (bk//pack, pack, bn)
    kp, _, bn = codes.shape
    return codes.reshape(kp * pack, bn).astype(jnp.float32)


def _dequant_matmul_kernel(x_ref, data_ref, scale_ref, o_ref, *, bits: int,
                           group_size: int, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = _unpack_block(data_ref[...], bits)          # (bk, bn) f32
    scales = scale_ref[...]                              # (bk//G, bn) f32
    bk, bn = codes.shape
    groups = bk // group_size
    w = codes.reshape(groups, group_size, bn) * scales.reshape(groups, 1, bn)
    w = w.reshape(bk, bn)                                # dequantized tile
    x = x_ref[...].astype(jnp.float32)                   # (bm, bk)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group_size", "block_m", "block_n", "block_k", "interpret"),
)
def dequant_matmul_pallas(x, data, scale, *, bits: int, group_size: int,
                          block_m: int = 128, block_n: int = 128,
                          block_k: int = 256, interpret: bool = False):
    """y = x @ dequant(data, scale).  Shapes must divide the block sizes."""
    m, k = x.shape
    kp, n = data.shape
    pack = PACK_FACTOR[bits]
    assert kp * pack == k, (kp, pack, k)
    assert block_k % group_size == 0, "block_k must cover whole quant groups"
    assert block_k % pack == 0
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (m, n, k)
    k_steps = k // block_k

    grid = (m // block_m, n // block_n, k_steps)
    kernel = functools.partial(
        _dequant_matmul_kernel, bits=bits, group_size=group_size, k_steps=k_steps
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // pack, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // group_size, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, data, scale)


# widest N block of the grouped kernels: a (bk/pack, 512) int8 tile, its
# f32 dequantized planes and the double buffers stay within scoped VMEM
_BLOCK_N = 512


def lane_block(n: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is <= ``cap``; ``n``
    itself when no such block exists (a block equal to the whole dim is
    always a legal Mosaic tiling)."""
    best = n
    for bn in range(128, min(cap, n) + 1, 128):
        if n % bn == 0:
            best = bn
    return best


def _grouped_operands(x, data, scale, *, bits: int, group_size: int,
                      block_k: int):
    """Re-block the (P, K) / (P, K//pack, N) / (P, K//G, N) operands so the
    k-block is a dimension of its own: every kernel block then spans its
    array's last two dims or a 128-multiple of them (the Mosaic tiling
    rule), whatever ``bk // group_size`` and ``bk // pack`` come to.

    x is de-interleaved by nibble position: byte row j of a packed tile
    holds the codes of K rows j*pack + i, so nibble plane i of the tile
    multiplies x[:, i, j] — no in-kernel sublane interleave is needed.
    Returns x (P, K/bk, pack, bk/pack) f32, data (P, K/bk, bk/pack, N),
    scale (P, K/bk, bk/G, N)."""
    p_, k = x.shape
    n = data.shape[-1]
    pack = PACK_FACTOR[bits]
    assert data.shape == (p_, k // pack, n), (data.shape, x.shape)
    assert block_k % group_size == 0 and group_size % pack == 0
    assert k % block_k == 0, (k, block_k)
    kb, kp = k // block_k, block_k // pack
    xt = x.astype(jnp.float32).reshape(p_, kb, kp, pack).transpose(0, 1, 3, 2)
    return (xt, data.reshape(p_, kb, kp, n),
            scale.reshape(p_, kb, block_k // group_size, n))


def _dequant_dot(x_ref, data_ref, scale_ref, *, bits: int, group_size: int):
    """x[1, bk] @ dequant(W)[bk, bn] for one tile.  Each nibble plane of the
    packed tile is sign-extended by a shift pair, scaled by its group's row
    (an aligned sublane broadcast: a group spans G/pack byte rows) and fed
    to the MXU against the matching de-interleaved x row.
    x_ref (pack, bk/pack) f32; data_ref (bk/pack, bn) int8;
    scale_ref (bk/G, bn) f32.  Returns (1, bn) f32."""
    pack = PACK_FACTOR[bits]
    b = data_ref[...].astype(jnp.int32)
    scales = scale_ref[...]
    rows = group_size // pack                  # byte rows per quant group
    s_rows = jnp.concatenate(
        [jnp.broadcast_to(scales[g:g + 1], (rows, scales.shape[1]))
         for g in range(scales.shape[0])], axis=0)          # (bk/pack, bn)
    out = None
    for i in range(pack):
        code = b if pack == 1 else (b << (32 - bits * (i + 1))) >> (32 - bits)
        w = code.astype(jnp.float32) * s_rows
        part = jnp.dot(x_ref[i:i + 1, :], w,
                       preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _grouped_dequant_kernel(x_ref, data_ref, scale_ref, o_ref, *, bits: int,
                            group_size: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += _dequant_dot(x_ref.at[0, 0], data_ref.at[0, 0],
                             scale_ref.at[0, 0], bits=bits,
                             group_size=group_size)


def _grouped_specs(kp, groups, bn, pack):
    """In-specs over the re-blocked operands for grid (N/bn, P, K/bk); the
    index maps ignore trailing scalar-prefetch refs."""
    return [
        pl.BlockSpec((1, 1, pack, kp), lambda j, ip, kk, *_: (ip, kk, 0, 0)),
        pl.BlockSpec((1, 1, kp, bn), lambda j, ip, kk, *_: (ip, kk, 0, j)),
        pl.BlockSpec((1, 1, groups, bn), lambda j, ip, kk, *_: (ip, kk, 0, j)),
    ]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group_size", "block_k", "interpret"))
def grouped_dequant_matmul_pallas(x, data, scale, *, bits: int,
                                  group_size: int, block_k: int = 512,
                                  interpret: bool = False):
    """y[p] = x[p] @ dequant(data[p], scale[p]) — the whole (P, K) pair
    batch in ONE kernel launch, grid (N/bn, P, K/bk), out-block accumulation
    over the k axis.  x: (P,K); data: (P,K//pack,N); scale: (P,K//group,N)."""
    p_ = x.shape[0]
    n = data.shape[-1]
    pack = PACK_FACTOR[bits]
    bn = lane_block(n, _BLOCK_N)
    xt, d4, s4 = _grouped_operands(x, data, scale, bits=bits,
                                   group_size=group_size, block_k=block_k)
    kb, kp, groups = d4.shape[1], d4.shape[2], s4.shape[2]
    kernel = functools.partial(_grouped_dequant_kernel, bits=bits,
                               group_size=group_size)
    out = pl.pallas_call(
        kernel,
        grid=(n // bn, p_, kb),
        in_specs=_grouped_specs(kp, groups, bn, pack),
        out_specs=pl.BlockSpec((1, 1, bn), lambda j, ip, kk: (ip, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p_, 1, n), jnp.float32),
        interpret=interpret,
    )(xt, d4, s4)
    return out[:, 0]


def _grouped_dequant_combine_kernel(rows_ref, x_ref, data_ref, scale_ref,
                                    o_ref, *, bits: int, group_size: int):
    ip = pl.program_id(1)
    kk = pl.program_id(2)
    # the output block index is rows_ref[ip]: consecutive pairs hitting the
    # same row revisit the block, so initialize only on the first visit
    first = (ip == 0) | (rows_ref[ip] != rows_ref[jnp.maximum(ip - 1, 0)])

    @pl.when(first & (kk == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += _dequant_dot(x_ref.at[0, 0], data_ref.at[0, 0],
                             scale_ref.at[0, 0], bits=bits,
                             group_size=group_size)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group_size", "num_rows", "block_k",
                     "interpret"))
def grouped_dequant_combine_pallas(x, data, scale, rows, weights, *,
                                   bits: int, group_size: int, num_rows: int,
                                   block_k: int = 512,
                                   interpret: bool = False):
    """Fused grouped dequant-GEMM + gated combine: out[rows[p]] +=
    weights[p] * (x[p] @ dequant(data[p], scale[p])), in one kernel launch.

    The gate weight is folded into x (the GEMM is linear in x).  The
    combine-scatter happens through a data-dependent OUTPUT index map (out
    block index = rows[p], a scalar-prefetch operand): pairs of the same
    token row land in the same VMEM-resident output block and accumulate in
    place.  The N-block axis is outermost so those revisits stay
    consecutive.  `rows` MUST therefore be sorted non-decreasing (the
    engine's pair builder emits them that way); pad pairs carry row ==
    num_rows, are clipped into range for the index map, and are neutralized
    by weight 0 — the wrapper zeroes rows no real pair visited (their pool
    buffers are never initialized by the kernel)."""
    p_ = x.shape[0]
    n = data.shape[-1]
    pack = PACK_FACTOR[bits]
    assert rows.shape == (p_,) and weights.shape == (p_,)
    bn = lane_block(n, _BLOCK_N)
    xw = x.astype(jnp.float32) * weights.astype(jnp.float32)[:, None]
    xt, d4, s4 = _grouped_operands(xw, data, scale, bits=bits,
                                   group_size=group_size, block_k=block_k)
    kb, kp, groups = d4.shape[1], d4.shape[2], s4.shape[2]
    rows_clip = jnp.clip(rows, 0, num_rows - 1).astype(jnp.int32)
    kernel = functools.partial(_grouped_dequant_combine_kernel, bits=bits,
                               group_size=group_size)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn, p_, kb),
            in_specs=_grouped_specs(kp, groups, bn, pack),
            out_specs=pl.BlockSpec((1, 1, bn),
                                   lambda j, ip, kk, rr: (rr[ip], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_rows, 1, n), jnp.float32),
        interpret=interpret,
    )(rows_clip, xt, d4, s4)
    hit = jnp.zeros((num_rows,), jnp.float32).at[rows].add(1.0, mode="drop")
    return jnp.where(hit[:, None] > 0, out[:, 0], 0.0)
