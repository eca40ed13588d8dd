"""Seeded random weights, made by the benchmark in one jitted call on the
device, in the dtype they are served in.

The tree has the layout the program's ``Model`` takes (embed, final_norm,
stacked ``blocks`` of attention + MoE layers; the harness checks it against
``jax.eval_shape(model.init)``), but the values are the benchmark's own, so
the reference can make the same weights again from the seed without
importing the program.  Linear weights are normal / sqrt(fan_in), norm
scales normal * 0.1 (applied as 1 + scale), routers float32.  The
(tied) embedding is normal * 1.28 / sqrt(d_model), 0.02 at d_model 4096, so
the logits spread alike at every width and a test at a small width reads
the output check's numbers on the same scale as a cell.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int):
    """A PRNG key from any non-negative whole number, wider than 32 bits
    included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    key = jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(words[1]) & 0x7FFFFFFF)


def _leaf_key(key, path: str):
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h & 0x7FFFFFFF)


def padded_vocab(m) -> int:
    return -(-int(m["vocab_size"]) // 256) * 256


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _stack_normal(key, lead, shape, scale, dtype):
    """(*lead, *shape) made one (*shape) slab at a time, so no float32 copy
    of a whole expert stack is ever live."""
    n = int(np.prod(lead))
    keys = jax.random.split(key, n)
    out = jax.lax.map(lambda k: _normal(k, shape, scale, dtype), keys)
    return out.reshape(*lead, *shape)


def build(m, key):
    """The weight tree for model dict ``m`` (a config file's "model")."""
    dt = jnp.dtype(m["dtype"])
    d, hd = int(m["d_model"]), int(m["head_dim"])
    hq, hkv, nb = int(m["num_heads"]), int(m["num_kv_heads"]), int(m["num_layers"])
    mo = m["moe"]
    e, f = int(mo["num_experts"]), int(mo["d_ff_expert"])
    wi_cols = 2 * f if m["ffn_activation"] == "swiglu" else f
    v = padded_vocab(m)

    def lin(path, shape):
        return _normal(_leaf_key(key, path), (nb, *shape), shape[0] ** -0.5, dt)

    def norm(path, n=None):
        lead = () if n is None else (n,)
        return {"scale": _normal(_leaf_key(key, path), (*lead, d), 0.1,
                                 jnp.float32)}

    layer = {
        "pre_norm": norm("pre_norm", nb),
        "attn": {"wq": lin("wq", (d, hq * hd)), "wk": lin("wk", (d, hkv * hd)),
                 "wv": lin("wv", (d, hkv * hd)), "wo": lin("wo", (hq * hd, d))},
        "ffn_norm": norm("ffn_norm", nb),
        "ffn": {
            "router": _normal(_leaf_key(key, "router"), (nb, d, e), d ** -0.5,
                              jnp.float32),
            "experts": {
                "wi": _stack_normal(_leaf_key(key, "experts.wi"), (nb, e),
                                    (d, wi_cols), d ** -0.5, dt),
                "wo": _stack_normal(_leaf_key(key, "experts.wo"), (nb, e),
                                    (f, d), f ** -0.5, dt),
            },
        },
    }
    params = {"embed": _normal(_leaf_key(key, "embed"), (v, d),
                               1.28 / d ** 0.5, dt),
              "final_norm": norm("final_norm"),
              "prefix": [], "blocks": [layer], "tail": []}
    if not m["tie_embeddings"]:
        params["lm_head"] = _normal(_leaf_key(key, "lm_head"), (d, v),
                                    d ** -0.5, dt)
    return params


def make(m, seed: int):
    """The weights for ``seed``, made on the default device in one call."""
    return jax.jit(build, static_argnums=0)(_Frozen(m), root_key(seed))


class _Frozen(dict):
    """A hashable model dict, so it can be a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))
