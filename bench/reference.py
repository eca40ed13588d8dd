"""Plain reference of the served models, in float32 at HIGHEST matmul
precision, written from the equations and importing nothing of the program.

Equations (those the program runs; each configuration file notes where they
depart from the published model): x = embed[token]; per layer
h = rmsnorm(x) * (1 + pre_scale); half-split RoPE on q and k; causal GQA
attention with scale 1/sqrt(head_dim); x += attn @ wo;
h = rmsnorm(x) * (1 + ffn_scale); router logits h @ router, softmax, top-k;
each routed expert is swiglu (silu(h @ wi[:, :f]) * (h @ wi[:, f:])) @ wo;
the outputs are mixed by the gates of the experts served, renormalized over
them (a skipped expert drops out); x += mix.  Logits are
rmsnorm(x) * (1 + final_scale) @ embed[:vocab].T.

A served expert's precision comes from the run's record: "hi" uses the bf16
weights as they are, "lo" the weights after the benchmark's own copy of the
groupwise symmetric int4 quantization (scale = absmax / 7 per group of
``group_size`` rows of the contraction dim), "skip" leaves the expert out.
At decode positions the record also fixes which experts were routed; the
comparison checks them against the reference's own router (``route_gap``).

``fp8=True`` is the control: every matmul operand rounded to float8 e4m3
with a scale per row or column of its contraction, the step below the
configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI, LO, SKIP = 0, 1, 2
HIGHEST = jax.lax.Precision.HIGHEST
# sequences are padded to a power of two of at least SEQ_MIN, and logits
# rows to a multiple of ROW_BUCKET, so a few programs serve every request
SEQ_MIN = 512
ROW_BUCKET = 64
FP8_MAX = 448.0


def _bucket(n: int, b: int) -> int:
    return max(b, -(-n // b) * b)


def seq_bucket(n: int) -> int:
    return max(SEQ_MIN, 1 << (int(n) - 1).bit_length())


def _q8(t, axis):
    """Round ``t`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(t), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec: str, a, b, fp8: bool):
    """einsum at HIGHEST; with fp8 both operands are first rounded along
    their contracted axes."""
    if fp8:
        ins, _ = spec.split("->")
        sa, sb = ins.split(",")
        shared = set(sa) & set(sb) - set(spec.split("->")[1])
        a = _q8(a, tuple(i for i, c in enumerate(sa) if c in shared))
        b = _q8(b, tuple(i for i, c in enumerate(sb) if c in shared))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs            # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def int4_dequant(w, bits: int, group: int):
    """The benchmark's copy of groupwise symmetric quantization along the
    contraction (first) dim, returned dequantized in float32."""
    k, n = w.shape
    qmax = (1 << (bits - 1)) - 1
    wg = w.astype(jnp.float32).reshape(k // group, group, n)
    scale = jnp.max(jnp.abs(wg), axis=1, keepdims=True) / qmax
    scale = jnp.where(scale == 0.0, 1.0, scale)
    codes = jnp.clip(jnp.round(wg / scale), -qmax, qmax)
    return (codes * scale).reshape(k, n)


def _expert(h, wi, wo, fp8):
    z = _einsum("td,dc->tc", h, wi, fp8)
    g, u = jnp.split(z, 2, axis=-1)
    return _einsum("tf,fd->td", jax.nn.silu(g) * u, wo, fp8)


@functools.partial(jax.jit, static_argnames=("mm", "fp8", "need_lo"))
def _layer(stack, li, x, n_valid, forced_e, forced_p, force, *, mm, fp8,
           need_lo):
    """Layer ``li`` of the stacked layer weights over a (Tb, D) sequence
    (rows >= n_valid are padding).  Returns (x, router logits (Tb, E),
    experts served (Tb, k))."""
    (d, hq, hkv, hd, k, theta, eps, bits, group) = mm
    lp = jax.tree_util.tree_map(lambda a: a[li], stack)
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    h = _rms(x, lp["pre_scale"], eps)
    q = _einsum("td,dn->tn", h, lp["wq"].astype(jnp.float32), fp8)
    kk = _einsum("td,dn->tn", h, lp["wk"].astype(jnp.float32), fp8)
    vv = _einsum("td,dn->tn", h, lp["wv"].astype(jnp.float32), fp8)
    q = _rope(q.reshape(t, hq, hd), pos, theta)
    kk = _rope(kk.reshape(t, hkv, hd), pos, theta)
    vv = vv.reshape(t, hkv, hd)
    g = hq // hkv
    qg = q.reshape(t, hkv, g, hd)
    s = _einsum("qhgd,khd->hgqk", qg, kk, fp8) / np.sqrt(hd)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _einsum("hgqk,khd->qhgd", p, vv, fp8).reshape(t, hq * hd)
    x = x + _einsum("tn,nd->td", o, lp["wo"].astype(jnp.float32), fp8)

    h = _rms(x, lp["ffn_scale"], eps)
    rl = _einsum("td,de->te", h, lp["router"].astype(jnp.float32), fp8)
    _, own = jax.lax.top_k(rl, k)
    # a forced expert id < 0 keeps the layer's own choice at that rank
    experts = jnp.where(force[:, None] & (forced_e >= 0), forced_e, own)
    prec = jnp.where(force[:, None], forced_p, HI)
    gate = jnp.take_along_axis(jax.nn.softmax(rl, axis=-1), experts, axis=1)
    w = jnp.where(prec == SKIP, 0.0, gate)
    w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-30)

    def body(y, xs):
        e, wi, wo = xs
        hit = experts == e
        c_hi = jnp.sum(jnp.where(hit & (prec == HI), w, 0.0), axis=1)
        wi32, wo32 = wi.astype(jnp.float32), wo.astype(jnp.float32)
        y = y + c_hi[:, None] * _expert(h, wi32, wo32, fp8)
        if need_lo:
            c_lo = jnp.sum(jnp.where(hit & (prec == LO), w, 0.0), axis=1)
            y = y + c_lo[:, None] * _expert(
                h, int4_dequant(wi32, bits, group),
                int4_dequant(wo32, bits, group), fp8)
        return y, None

    n_exp = lp["wi"].shape[0]
    y, _ = jax.lax.scan(body, jnp.zeros_like(x),
                        (jnp.arange(n_exp), lp["wi"], lp["wo_e"]))
    return x + y, rl, experts


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "fp8"))
def _logits(scale, table, x, *, eps, vocab, fp8):
    h = _rms(x, scale, eps)
    return _einsum("nd,vd->nv", h, table[:vocab].astype(jnp.float32), fp8)


class Reference:
    """The reference for one configuration, over weights ``params`` (the
    benchmark's weight tree)."""

    def __init__(self, params, m: Dict, eng: Dict, *, fp8: bool = False):
        self.m, self.fp8 = m, fp8
        self.th = tuple(float(t) for t in eng["thresholds"])
        mo = m["moe"]
        self.k = int(mo["top_k"])
        self.mm = (int(m["d_model"]), int(m["num_heads"]), int(m["num_kv_heads"]),
                   int(m["head_dim"]), self.k, float(m["rope_theta"]),
                   float(m["norm_eps"]), int(eng["lo_bits"]), int(eng["group_size"]))
        blk = params["blocks"][0]
        self.n_layers = int(m["num_layers"])
        self.stack = {
            "pre_scale": blk["pre_norm"]["scale"],
            "ffn_scale": blk["ffn_norm"]["scale"],
            "wq": blk["attn"]["wq"], "wk": blk["attn"]["wk"],
            "wv": blk["attn"]["wv"], "wo": blk["attn"]["wo"],
            "router": blk["ffn"]["router"],
            "wi": blk["ffn"]["experts"]["wi"],
            "wo_e": blk["ffn"]["experts"]["wo"]}
        self.embed = params["embed"]
        # (V_pad, D): the tied embedding, or the untied head transposed
        self.table = (params["lm_head"].T if "lm_head" in params
                      else params["embed"])
        self.final_scale = params["final_norm"]["scale"]

    def forward(self, tokens: np.ndarray, forced: Optional[Dict] = None,
                rows: Sequence[int] = ()):
        """Run ``tokens``; ``forced`` = {"pos": (n,), "experts": (n, L, k),
        "prec": (n, L, k), optional "mask": (n, L)} fixes routing (an id < 0
        keeps the layer's own) and precision at those positions and layers;
        the rest route themselves, all hi.  Returns (logits at ``rows``
        (len(rows), V), [router logits (T, E)] per layer, [experts served
        (T, k)] per layer), all host arrays."""
        t = len(tokens)
        tb = seq_bucket(t)
        n_layers = self.n_layers
        fe = np.zeros((n_layers, tb, self.k), np.int32)
        fp = np.zeros((n_layers, tb, self.k), np.int32)
        force = np.zeros((n_layers, tb), bool)
        if forced is not None and len(forced["pos"]):
            pos = np.asarray(forced["pos"])
            for li in range(n_layers):
                fe[li, pos] = forced["experts"][:, li]
                fp[li, pos] = forced["prec"][:, li]
                force[li, pos] = forced.get("mask", np.ones((len(pos), n_layers),
                                                            bool))[:, li]
        need_lo = bool((fp == LO).any())
        toks = np.zeros(tb, np.int32)
        toks[:t] = tokens
        x = jnp.take(self.embed, jnp.asarray(toks), axis=0).astype(jnp.float32)
        rls, chosen = [], []
        for li in range(n_layers):
            x, rl, ex = _layer(
                self.stack, jnp.int32(li), x, jnp.int32(t),
                jnp.asarray(fe[li]), jnp.asarray(fp[li]),
                jnp.asarray(force[li]), mm=self.mm, fp8=self.fp8,
                need_lo=need_lo)
            rls.append(np.asarray(rl[:t]))
            chosen.append(np.asarray(ex[:t]))
        rows = np.asarray(rows, np.int32)
        nb = _bucket(max(len(rows), 1), ROW_BUCKET)
        idx = np.zeros(nb, np.int32)
        idx[:len(rows)] = rows
        lg = _logits(self.final_scale, self.table, x[jnp.asarray(idx)],
                     eps=self.mm[6], vocab=int(self.m["vocab_size"]),
                     fp8=self.fp8)[:len(rows)]
        return np.asarray(lg), rls, chosen
