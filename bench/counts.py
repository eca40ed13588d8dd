"""Operations and bytes the served work needs, from shapes alone.

``m`` is a configuration file's "model" dict, ``eng`` its "engine" dict.
Operations count a multiply-add as 2.  These are what the algorithm needs,
not what a kernel happens to move: padding, gathers and pairs a kernel
computes for nothing are left out.
"""

from __future__ import annotations

from typing import Dict, Iterable


def _dims(m: Dict):
    mo = m["moe"]
    return (int(m["d_model"]), int(m["num_heads"]), int(m["num_kv_heads"]),
            int(m["head_dim"]), int(mo["num_experts"]), int(mo["top_k"]),
            int(mo["d_ff_expert"]), int(m["vocab_size"]), int(m["num_layers"]))


def expert_flops(m: Dict) -> int:
    """One routed swiglu expert on one token: (D, 2F) then (F, D)."""
    d, *_ , f, _v, _l = _dims(m)
    return 2 * d * 2 * f + 2 * f * d


def token_flops(m: Dict, context: int, *, lm_head: bool = True) -> int:
    """One token through every layer at ``context`` cached positions
    (itself included): q/k/v/o projections, attention over the context,
    router, top_k experts, and the lm_head when it yields a token."""
    d, hq, hkv, hd, e, k, f, v, layers = _dims(m)
    proj = 2 * d * (hq * hd + 2 * hkv * hd) + 2 * hq * hd * d
    attn = 4 * hq * hd * int(context)
    per_layer = proj + attn + 2 * d * e + k * expert_flops(m)
    return layers * per_layer + (2 * d * v if lm_head else 0)


def prompt_flops(m: Dict, prompt_len: int, start: int = 0, end: int = None) -> int:
    """Prompt positions [start, end) of a ``prompt_len`` prompt: each at
    its own causal context, the lm_head once, at the last position."""
    end = prompt_len if end is None else end
    n = end - start
    d, hq, hkv, hd, e, k, f, v, layers = _dims(m)
    ctx_sum = (end * (end + 1) - start * (start + 1)) // 2
    fixed = token_flops(m, 0, lm_head=False) * n
    attn = layers * 4 * hq * hd * ctx_sum
    head = 2 * d * v if end == prompt_len else 0
    return fixed + attn + head


def lo_pair_cost(m: Dict, eng: Dict, pairs: int, rows: int):
    """(operations, bytes) of the two lo kernels for ``pairs`` real (row,
    expert) pairs over ``rows`` token rows: packed int4 codes and float32
    group scales of wi and wo, float32 activations in and out."""
    d, *_, f, _v, _l = _dims(m)
    bits, group = int(eng["lo_bits"]), int(eng["group_size"])
    wi_n, wo_n = 2 * f, d
    codes = (d * wi_n + f * wo_n) * bits // 8
    scales = (d // group * wi_n + f // group * wo_n) * 4
    acts = pairs * (d + wi_n + f) * 4 + rows * d * 4
    return pairs * expert_flops(m), pairs * (codes + scales) + acts


def paged_attn_cost(m: Dict, lengths: Iterable[int]):
    """(operations, bytes) of paged flash decode for live rows holding
    ``lengths`` cached tokens each: bf16 K and V actually attended, bf16 q
    in, float32 o out."""
    _d, hq, hkv, hd, *_ = _dims(m)
    lengths = [int(n) for n in lengths]
    kv = sum(lengths) * hkv * hd * 2 * 2
    qo = len(lengths) * hq * hd * (2 + 4)
    return 4 * hq * hd * sum(lengths), kv + qo


def least_time(flops: float, nbytes: float, peak: Dict) -> float:
    """Roofline: the larger of compute time at the bf16 peak and memory
    time at the HBM bandwidth."""
    return max(flops / float(peak["bf16_flops"]),
               nbytes / float(peak["hbm_bytes_per_s"]))
