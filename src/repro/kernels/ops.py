"""Public jit'd wrappers for the Pallas kernels, with shape padding, a
CPU-friendly execution policy, and dispatch observability.

On the TPU target the kernels run compiled; on CPU the pallas path runs in
``interpret=True`` mode (Pallas executes the kernel body in Python) so tests
validate the real kernel body.  ``mode`` selects:

    "auto"      pallas on TPU, the XLA reference path elsewhere — unless the
                ``REPRO_KERNEL_MODE`` env var ("xla" / "pallas") overrides
                the choice (CI sets "pallas" to run every kernel body in
                interpret mode on CPU)
    "pallas"    force the pallas path (compiled on TPU, interpret elsewhere)
    "xla"       reference dense path (dequantize + dot) — used for big CPU
                smoke tests where interpret-mode python execution would be
                too slow

Every dispatch records which implementation ran in a module-level counter
(``dispatch_counts()``), surfaced through ``engine.stats()["kernel_dispatch"]``
so a misconfigured run can't silently benchmark the einsum path.  Counters
tick when an op is dispatched OR traced into a jit computation: under jit
a nonzero ``<op>.pallas`` count proves the pallas kernel is in the compiled
graph (steady-state calls replay the trace without re-counting).
"""

from __future__ import annotations

import math
import os
from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dequant_matmul import (
    dequant_matmul_pallas,
    grouped_dequant_combine_pallas,
    grouped_dequant_matmul_pallas,
)
from repro.kernels.flash_decode import (
    flash_decode_pallas,
    paged_flash_decode_pallas,
)
from repro.kernels.stacked_gating import gating_topk_pallas, stacked_gating_pallas
from repro.quant.quantize import PACK_FACTOR, QTensor, dequantize

_DISPATCH_COUNTS: Dict[str, int] = {}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: str) -> str:
    """Collapse "auto" to the implementation that will actually run."""
    if mode == "auto":
        env = os.environ.get("REPRO_KERNEL_MODE", "")
        if env in ("xla", "pallas"):
            return env
        return "pallas" if _on_tpu() else "xla"
    return mode


def _record(op: str, impl: str) -> None:
    key = f"{op}.{impl}"
    _DISPATCH_COUNTS[key] = _DISPATCH_COUNTS.get(key, 0) + 1


def _record_pallas(op: str, interpret: bool) -> None:
    _record(op, "pallas_interpret" if interpret else "pallas")


def dispatch_counts() -> Dict[str, int]:
    """Copy of the per-op dispatch counters, keyed ``"<op>.<impl>"`` with
    impl one of xla / pallas / pallas_interpret."""
    return dict(_DISPATCH_COUNTS)


def reset_dispatch_counts() -> None:
    _DISPATCH_COUNTS.clear()


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _k_block(k: int, group_size: int, pack: int, cap: int) -> int:
    """Largest k-block <= cap that divides K and covers whole quant groups
    and packed bytes (K is a multiple of both by the quant layout).  Blocks
    whose packed width (bk / pack) is a multiple of the 128-lane tile are
    preferred: the compiled grouped kernels need them."""
    # smallest legal tile: lcm(group_size, pack); group_size is a multiple
    # of pack for every supported layout, so group_size itself is legal
    step = group_size if group_size % pack == 0 else group_size * pack
    lane_step = math.lcm(step, 128 * pack)
    for s in (lane_step, step):
        fits = [m for m in range(s, min(cap, k) + 1, s) if k % m == 0]
        if fits:
            return fits[-1]
    return step


def dequant_matmul(x, q: QTensor, *, mode: str = "auto",
                   block_m: int = 128, block_n: int = 128, block_k: int = 256):
    """y = x @ dequant(q), fused.  x: (..., K); q: K x N quantized."""
    if _resolve(mode) == "xla":
        _record("dequant_matmul", "xla")
        return ref.dequant_matmul_ref(x, q)

    interpret = not _on_tpu()
    _record_pallas("dequant_matmul", interpret)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m, n = x2.shape[0], q.data.shape[-1]
    pack = PACK_FACTOR[q.bits]

    bm = min(block_m, _pad_to(m, 8))
    bk = min(block_k, k)
    bn = min(block_n, n)
    mp, np_, kp = _pad_to(m, bm), _pad_to(n, bn), _pad_to(k, bk)
    if (mp, np_, kp) != (m, n, k):
        x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
        data = jnp.pad(q.data, ((0, (kp - k) // pack), (0, np_ - n)))
        scale = jnp.pad(q.scale, ((0, (kp - k) // q.group_size), (0, np_ - n)))
    else:
        data, scale = q.data, q.scale
    out = dequant_matmul_pallas(
        x2, data, scale, bits=q.bits, group_size=q.group_size,
        block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return out[:m, :n].reshape(*lead, n)


def grouped_dequant_matmul(x, data, scale, *, bits: int, group_size: int,
                           mode: str = "auto", block_k: int = 512):
    """Batched per-expert fused dequant GEMM: y[p] = x[p] @ dequant(data[p]).

    This is the grouped-decode hot path: every active (token row, expert)
    pair of a MoE layer becomes one slice p, so the whole layer's low-
    precision expert compute is a single dispatch instead of O(batch*top_k)
    tiny calls.

        x      (P, K)             activations, one row per pair
        data   (P, K // pack, N)  packed codes gathered from the lo pool
        scale  (P, K // group, N) groupwise scales
        out    (P, N)             f32

    The pallas path is ONE kernel launch over the (P, K/bk) grid — the
    int-unpack, scale-multiply, and GEMM happen per tile in VREGs; the
    reference path is dense dequantize + einsum (one XLA dispatch)."""
    if _resolve(mode) == "xla":
        _record("grouped_dequant_matmul", "xla")
        q = QTensor(data, scale, bits, group_size, x.shape[-1])
        w = dequantize(q)                       # (P, K, N) f32
        return jnp.einsum("pk,pkn->pn", x.astype(jnp.float32), w,
                          preferred_element_type=jnp.float32)

    interpret = not _on_tpu()
    _record_pallas("grouped_dequant_matmul", interpret)
    k = x.shape[-1]
    bk = _k_block(k, group_size, PACK_FACTOR[bits], block_k)
    return grouped_dequant_matmul_pallas(
        x, data, scale, bits=bits, group_size=group_size, block_k=bk,
        interpret=interpret)


def grouped_dequant_combine(x, data, scale, rows, weights, *, bits: int,
                            group_size: int, num_rows: int,
                            mode: str = "auto", block_k: int = 512):
    """Fused grouped dequant-GEMM + gated combine over the padded pair grid:
    out[rows[p]] += weights[p] * (x[p] @ dequant(data[p], scale[p])).

        x        (P, K)             per-pair activations
        data     (P, K//pack, N)    packed codes
        scale    (P, K//group, N)   groupwise scales
        rows     (P,) int           destination token row, sorted
                                    non-decreasing; pads carry num_rows
        weights  (P,) f32           gate weight per pair (0 for pads)
        out      (num_rows, N) f32

    The pallas path scatters through a data-dependent output index map so
    unpack, GEMM, gating, and combine are one kernel; pad rows (row ==
    num_rows) are dropped in-kernel by weight 0 + the wrapper's hit mask.
    The reference path is dequantize + einsum + ``.at[rows].add`` with
    mode="drop"."""
    if _resolve(mode) == "xla":
        _record("grouped_dequant_combine", "xla")
        return ref.grouped_dequant_combine_ref(
            x, data, scale, rows, weights, bits=bits, group_size=group_size,
            num_rows=num_rows)

    interpret = not _on_tpu()
    _record_pallas("grouped_dequant_combine", interpret)
    k = x.shape[-1]
    bk = _k_block(k, group_size, PACK_FACTOR[bits], block_k)
    return grouped_dequant_combine_pallas(
        x, data, scale, rows, weights, bits=bits, group_size=group_size,
        num_rows=num_rows, block_k=bk, interpret=interpret)


def stacked_gating(x, gates, *, mode: str = "auto", block_d: int = 512):
    """logits (P, B, E) for P stacked gate matrices; see stacked_gating.py."""
    if _resolve(mode) == "xla":
        _record("stacked_gating", "xla")
        return ref.stacked_gating_ref(x, gates)
    interpret = not _on_tpu()
    _record_pallas("stacked_gating", interpret)
    b, d = x.shape
    p, _, e = gates.shape
    bd = min(block_d, d)
    dp = _pad_to(d, bd)
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, dp - d)))
        gates = jnp.pad(gates, ((0, 0), (0, dp - d), (0, 0)))
    return stacked_gating_pallas(x, gates, block_d=bd, interpret=interpret)


def gating_topk(x, gates, *, top_k: int, mode: str = "auto",
                block_d: int = 512):
    """Fused router: stacked gate matmul + softmax + top-k in one pass.

        x      (B, D)      activations
        gates  (P, D, E)   stacked router weights
        out    (logits (P,B,E) f32, vals (P,B,K) f32 softmax probs of the
                selected experts, idx (P,B,K) i32)

    Ties select the lowest expert index on both paths."""
    if _resolve(mode) == "xla":
        _record("gating_topk", "xla")
        return ref.gating_topk_ref(x, gates, top_k=top_k)
    interpret = not _on_tpu()
    _record_pallas("gating_topk", interpret)
    b, d = x.shape
    bd = min(block_d, d)
    dp = _pad_to(d, bd)
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, dp - d)))
        gates = jnp.pad(gates, ((0, 0), (0, dp - d), (0, 0)))
    return gating_topk_pallas(x, gates, top_k=top_k, block_d=bd,
                              interpret=interpret)


def flash_decode(q, k, v, lengths, *, mode: str = "auto", block_s: int = 256):
    """Single-token decode attention; expands GQA kv heads to q heads.
    q: (B,Hq,hd); k/v: (B,S,Hkv,hd); lengths: (B,)."""
    b, hq, hd = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        g = hq // hkv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    if _resolve(mode) == "xla":
        _record("flash_decode", "xla")
        return ref.flash_decode_ref(q, k, v, lengths)
    interpret = not _on_tpu()
    _record_pallas("flash_decode", interpret)
    s = k.shape[1]
    bs = min(block_s, s)
    sp = _pad_to(s, bs)
    if sp != s:
        k = jnp.pad(k, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    return flash_decode_pallas(q, k, v, lengths, block_s=bs, interpret=interpret)


def paged_flash_decode(q, pages_k, pages_v, table, lengths, *,
                       mode: str = "auto", scale=None, softcap: float = 0.0):
    """Decode attention straight out of the paged KV pool — the page table
    drives the kernel's K/V block index maps, so the dense (B, maxp*psz)
    gathered cache view is never materialized (the ref oracle gathers).

        q        (B, Hq, hd)        current-token queries
        pages_k  (P, psz, Hkv, hd)  shared page pool (pages_v alike)
        table    (B, maxp) int      physical page per logical page
        lengths  (B,) int           valid cache tokens per slot
        out      (B, Hq, hd) f32    zeros where lengths == 0"""
    if _resolve(mode) == "xla":
        _record("paged_flash_decode", "xla")
        return ref.paged_flash_decode_ref(q, pages_k, pages_v, table, lengths,
                                          scale=scale, softcap=softcap)
    interpret = not _on_tpu()
    _record_pallas("paged_flash_decode", interpret)
    return paged_flash_decode_pallas(q, pages_k, pages_v, table, lengths,
                                     interpret=interpret, scale=scale,
                                     softcap=softcap)
