"""Flash-decode attention Pallas kernel: one query token per sequence
against a long KV cache, online-softmax over KV blocks.

Decode attention is the second memory-bound hot-spot of MoE serving (after
expert weights): the whole KV cache streams through the MXU once per token.
The flash formulation keeps one (block_s, head_dim) KV tile in VMEM at a
time and carries running max/denominator statistics, so the score vector is
never materialized in HBM — on TPU this bounds VMEM use to the tile size
and lets the DMA pipeline hide the HBM streaming.

Contract:
    q        (B, H, hd)        current-token queries (kv heads pre-expanded)
    k, v     (B, S, H, hd)     cache
    lengths  (B, 1)            #valid cache slots per sequence (<= S)
    out      o (B, H, hd) fp32, m (B, H, 1), l (B, H, 1)
Final output = o / l (done by the wrapper).

Grid (B, H, S/block_s); the (o, m, l) blocks are revisited across the S axis
and updated with the standard rescaling recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref,
                         *, block_s: int, scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, :].astype(jnp.float32)               # (hd,)
    k = k_ref[0, :, 0, :].astype(jnp.float32)            # (bs, hd)
    v = v_ref[0, :, 0, :].astype(jnp.float32)            # (bs, hd)
    length = len_ref[0, 0]

    logits = (k @ q) * scale                              # (bs,)
    pos = s_idx * block_s + jax.lax.iota(jnp.int32, block_s)
    logits = jnp.where(pos < length, logits, NEG_INF)

    m_old = m_ref[0, 0, 0]
    m_new = jnp.maximum(m_old, jnp.max(logits))
    p = jnp.exp(logits - m_new)                           # (bs,)
    corr = jnp.exp(m_old - m_new)
    l_ref[0, 0, 0] = l_ref[0, 0, 0] * corr + jnp.sum(p)
    o_ref[0, 0, :] = o_ref[0, 0, :] * corr + p @ v
    m_ref[0, 0, 0] = m_new


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_pallas(q, k, v, lengths, *, block_s: int = 256,
                        interpret: bool = False, scale: float | None = None):
    """Returns attention output (B, H, hd) fp32."""
    b, h, hd = q.shape
    s = k.shape[1]
    assert k.shape == (b, s, h, hd) and v.shape == k.shape
    assert s % block_s == 0, (s, block_s)
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    lengths2 = lengths.reshape(b, 1).astype(jnp.int32)

    kernel = functools.partial(_flash_decode_kernel, block_s=block_s,
                               scale=scale)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b, h, s // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda bb, hh, ss: (bb, hh, 0)),
            pl.BlockSpec((1, block_s, 1, hd), lambda bb, hh, ss: (bb, ss, hh, 0)),
            pl.BlockSpec((1, block_s, 1, hd), lambda bb, hh, ss: (bb, ss, hh, 0)),
            pl.BlockSpec((1, 1), lambda bb, hh, ss: (bb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hd), lambda bb, hh, ss: (bb, hh, 0)),
            pl.BlockSpec((1, 1, 1), lambda bb, hh, ss: (bb, hh, 0)),
            pl.BlockSpec((1, 1, 1), lambda bb, hh, ss: (bb, hh, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, lengths2)
    return o / jnp.maximum(l, 1e-30)


def _paged_flash_decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref,
                               o_ref, m_sc, l_sc, acc_sc, *, page_size: int,
                               scale: float, softcap: float):
    bb = pl.program_id(0)
    pp = pl.program_id(1)

    @pl.when(pp == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # GQA as a batch over kv heads: the g query heads of kv head h score
    # only that head's page rows
    q = q_ref[0].astype(jnp.float32)                          # (Hkv, g, hd)
    k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)      # (Hkv, psz, hd)
    v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
    length = len_ref[bb]

    logits = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale           # (Hkv, g, psz)
    if softcap and softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    # token t of a slot lives at LOGICAL page t // psz: mask by the logical
    # page index pp, not the physical page the table maps it to
    pos = pp * page_size + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 2)
    valid = pos < length
    logits = jnp.where(valid, logits, NEG_INF)

    m_old = m_sc[...]                                         # (Hkv, g, 1)
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=2, keepdims=True))
    p = jnp.exp(logits - m_new)
    # explicit re-mask: when EVERY position seen so far is invalid (length
    # 0), m_new == NEG_INF and exp(NEG_INF - NEG_INF) would turn the mask
    # into uniform weights; zeroed p keeps l at 0 so the output is 0
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_old - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                   # (Hkv, g, hd)
    m_sc[...] = m_new

    @pl.when(pp == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "scale", "softcap"))
def paged_flash_decode_pallas(q, pages_k, pages_v, table, lengths, *,
                              interpret: bool = False,
                              scale: float | None = None,
                              softcap: float = 0.0):
    """Flash decode straight out of a paged KV pool: the page table rides in
    as a scalar-prefetch operand and drives the K/V block index maps, so each
    grid step DMAs exactly one physical (psz, Hkv, hd) page — the dense
    (B, maxp*psz) gathered cache view is never materialized.

        q        (B, Hq, hd)           current-token queries
        pages_k  (P, psz, Hkv, hd)     shared page pool (pages_v alike)
        table    (B, maxp) int         physical page id per logical page
        lengths  (B,) int              #valid cache tokens per slot

    Grid (B, maxp): one step reads a whole page for all kv heads and scores
    it against the query heads grouped by kv head, (Hkv, g, hd) against
    (Hkv, psz, hd), so no repeated K/V is ever built.  The running max /
    denominator / accumulator live in VMEM scratch across the page axis.
    Returns (B, Hq, hd) f32; rows with length 0 return exact zeros."""
    b, hq, hd = q.shape
    num_pages, psz, hkv, hd2 = pages_k.shape
    assert hd2 == hd and pages_v.shape == pages_k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    maxp = table.shape[1]
    assert table.shape == (b, maxp)
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    kernel = functools.partial(_paged_flash_decode_kernel, page_size=psz,
                               scale=scale, softcap=softcap)
    kv_spec = pl.BlockSpec((1, psz, hkv, hd),
                           lambda bb, pp, tab, ln: (tab[bb, pp], 0, 0, 0))
    q_spec = pl.BlockSpec((1, hkv, g, hd),
                          lambda bb, pp, tab, ln: (bb, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(b, hkv, g, hd), pages_k, pages_v)
    return out.reshape(b, hq, hd)
