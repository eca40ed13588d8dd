#!/usr/bin/env python3
"""Serve HOBBIT end to end on one TPU chip at published MoE widths.

    python3 chip_smoke.py [--layers 2] [--seed 0]

The models are Phi-3.5-MoE (d_model 4096, 16 experts of d_ff 6400, top-2,
32 query / 8 kv heads, vocab 32064, bf16) and Mixtral-8x7B (d_model 4096,
8 experts of d_ff 14336, top-2, 32 / 8 heads, vocab 32000, bf16), cut in
depth, with random weights made from --seed.  Four phases run in this one
process; the last three go through the calls ``launch/serve.py`` makes
(``BackendConfig`` -> ``make_backend`` -> ``BatchingServer``):

0. kernels (Phi-MoE and Mixtral widths): one layer's lo expert GEMMs through
   the ops dispatch against the ref.py oracles, every N block included.
1. parity (Phi-MoE, 1 layer): ``HobbitBackend`` (paged KV, every expert hi, no
   prefetch) against ``DenseBackend`` (dense KV, no Pallas kernel) on the
   same prompts — prefill logits and the first decode steps, both fed the
   dense backend's greedy tokens: argmax equal, logits within a bf16
   bound.  One layer: the all-hi pool holds every expert, and
   ``grouped_ffn`` copies the pool once more per layer call, so two layers
   would not fit 16 GB.
2. mixed precision (Phi-MoE, --layers): four requests of 64-256 prompt
   tokens and 16 new tokens through ``BatchingServer`` (2 slots) on a
   ``HobbitBackend`` whose hi/lo pools hold fewer experts than the model
   has, with the default thresholds.  Every logits row a live slot produced
   is finite, lo copies were loaded, and the four main-path kernels ran
   compiled.
3. mixed precision (Mixtral-8x7B, 1 layer): the same at Mixtral's widths.
   One layer: its float32 overflow buffers and the pool copy inside
   ``grouped_ffn`` leave no room for a second.

Times, rates and byte counts printed on the way are smoke figures, not
benchmark numbers.  The last line of stdout is one JSON object naming the
device.  Exits non-zero, printing no result, without a TPU, with
REPRO_KERNEL_MODE set, without the repro package next to this file, or when
any check fails.
"""

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
MAIN_PATH_KERNELS = ("gating_topk", "grouped_dequant_matmul",
                     "grouped_dequant_combine", "paged_flash_decode")
# parity bound on |hobbit - dense| logits, relative to the largest dense
# logit: both paths round activations to bf16 (8 mantissa bits) at every
# layer boundary, in different orders (paged vs dense attention, per-pair
# vs capacity-dispatched expert GEMMs) — 2^-4 allows a few such roundings
PARITY_RTOL = 2.0 ** -4
# lo kernel bound on |kernel - oracle|, relative to the oracle's largest
# value: should the MXU run the in-kernel f32 dot as one bf16 pass, each
# product is off by about 2^-8 and the K-term sums by about 2^-8 of their
# spread; an N block or pair mapped to the wrong place is off by O(1)
KERNEL_RTOL = 2.0 ** -6


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def model_config(arch: str, layers: int):
    from repro.configs import get_config
    base = get_config(arch)
    # capacity factor E / top_k: the dense backend's capacity-based expert
    # dispatch can then drop no token, so it computes the same routed
    # mixture as the engine's per-pair path
    moe = dataclasses.replace(
        base.moe, capacity_factor=base.moe.num_experts / base.moe.top_k)
    return dataclasses.replace(base, num_layers=layers, moe=moe).validate()


def init_params(model, seed: int):
    """Random weights from ``seed``, made in one jitted program: eager
    init would hold every layer twice while it stacks them."""
    import jax
    return jax.jit(model.init)(jax.random.PRNGKey(seed))


def device_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def kernel_phase(arch: str, seed: int, *, batch: int = 2) -> None:
    """One MoE layer's lo expert step at ``arch``'s widths through the ops
    dispatch (``mode="auto"``, the compiled kernels on a TPU) against the
    ref.py oracles run by XLA at full f32 precision: the ``wi`` GEMM over
    every N block and the ``wo`` GEMM + gated combine, with the pair grid
    padded as the engine pads it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import EngineConfig
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.quant.quantize import quantize

    cfg = model_config(arch, 1)
    ecfg = EngineConfig()
    d, f, top_k = cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.top_k
    wi_cols = 2 * f if cfg.ffn_activation == "swiglu" else f
    q = dict(bits=ecfg.lo_bits, group_size=ecfg.group_size)
    p = batch * top_k + 2                           # two pad pairs

    @jax.jit
    def operands(key):
        kx, ki, kh, ko = jax.random.split(key, 4)
        wi = quantize(jax.random.normal(ki, (p, d, wi_cols)) * d ** -0.5, **q)
        wo = quantize(jax.random.normal(ko, (p, f, d)) * f ** -0.5, **q)
        return (jax.random.normal(kx, (p, d), jnp.bfloat16), wi,
                jax.random.normal(kh, (p, f), jnp.bfloat16), wo)

    x, wi, h, wo = operands(jax.random.PRNGKey(seed + 2))
    rows = jnp.asarray(np.repeat(np.arange(batch + 1), top_k)[:p], jnp.int32)
    wts = jnp.asarray(np.where(np.arange(p) < batch * top_k, 0.5, 0.0),
                      jnp.float32)
    kops.reset_dispatch_counts()
    t0 = time.perf_counter()
    got = (kops.grouped_dequant_matmul(x, wi.data, wi.scale, **q),
           kops.grouped_dequant_combine(h, wo.data, wo.scale, rows, wts,
                                        num_rows=batch, **q))
    jax.block_until_ready(got)
    wall = time.perf_counter() - t0
    kd = kops.dispatch_counts()
    with jax.default_matmul_precision("highest"):
        want = (kops.grouped_dequant_matmul(x, wi.data, wi.scale, mode="xla",
                                            **q),
                ref.grouped_dequant_combine_ref(h, wo.data, wo.scale, rows,
                                                wts, num_rows=batch, **q))
    for op, g_, w_ in zip(("grouped_dequant_matmul", "grouped_dequant_combine"),
                          got, want):
        g_, w_ = np.asarray(g_), np.asarray(w_)
        check(g_.shape == w_.shape, f"kernels {arch}: {op} shape "
              f"{g_.shape} != oracle {w_.shape}")
        check(bool(np.isfinite(g_).all()), f"kernels {arch}: {op} not finite")
        scale = float(np.abs(w_).max())
        rel = float(np.abs(g_ - w_).max()) / scale
        log(f"kernels {arch}: {op} {tuple(g_.shape)} "
            f"max|d|/max|oracle|={rel!r} (bound {KERNEL_RTOL!r}) "
            f"max|oracle|={scale!r}")
        check(rel <= KERNEL_RTOL, f"kernels {arch}: {op} differs from its "
              f"oracle by {rel!r} of its largest value")
        check(kd.get(f"{op}.pallas", 0) == 1,
              f"kernels {arch}: {op} did not dispatch the compiled kernel")
    log(f"kernels {arch}: passed ({wall:.3f} s, compile included)")


def parity_phase(dev, seed: int, *, batch: int = 2, prompt_len: int = 96,
                 steps: int = 4, page: int = 64) -> None:
    import jax
    import numpy as np

    from repro.core import EngineConfig, Thresholds
    from repro.models import build_model
    from repro.serving.api import BackendConfig, make_backend

    cfg = model_config("phi-moe", 1)
    t0 = time.perf_counter()
    model = build_model(cfg)
    params = init_params(model, seed)
    dense = make_backend(BackendConfig(kind="dense"), model, params)
    hobbit = make_backend(BackendConfig(
        kind="hobbit", paged=True, page_size=page, prefill_chunk=page,
        engine=EngineConfig(
            hi_slots=cfg.num_layers * cfg.moe.num_experts, lo_slots=1,
            thresholds=Thresholds(1.0, 1.0), prefetch=False)), model, params)
    log(f"parity: setup {time.perf_counter() - t0:.3f} s "
        f"(init + host expert copies); {device_bytes(dev)}")

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len), np.int32)
    t0 = time.perf_counter()
    dense.start_batch(batch, prompt_len + steps + 1)
    hobbit.start_batch(batch, prompt_len + steps + 1)
    ref, got = [dense.prefill(prompts)], [hobbit.prefill(prompts)]
    for _ in range(steps):
        tok = ref[-1].argmax(-1).astype(np.int32)
        ref.append(dense.step(tok))
        got.append(hobbit.step(tok))
    stats = hobbit.stats()
    hobbit.close()
    log(f"parity: prefill + {steps} decode steps on both backends "
        f"{time.perf_counter() - t0:.3f} s (compile included); "
        f"loads_hi={stats['loads_hi']} loads_lo={stats['loads_lo']}")

    worst = 0.0
    for i, (r, g) in enumerate(zip(ref, got)):
        what = "prefill" if i == 0 else f"decode step {i}"
        check(bool(np.isfinite(r).all() and np.isfinite(g).all()),
              f"parity: non-finite logits at {what}")
        scale = float(np.abs(r).max())
        diff = float(np.abs(g - r).max())
        tol = PARITY_RTOL * max(scale, 1.0)
        worst = max(worst, diff / max(scale, 1.0))
        am_r, am_g = r.argmax(-1), g.argmax(-1)
        log(f"parity: {what}: max|dlogit|={diff!r} max|logit|={scale!r} "
            f"tol={tol!r} argmax_equal={int((am_r == am_g).sum())}/{batch}")
        check(diff <= tol, f"parity: {what}: max |hobbit - dense| logit "
              f"{diff!r} exceeds {tol!r}")
        check(bool((am_r == am_g).all()),
              f"parity: {what}: hobbit argmax {am_g} != dense argmax {am_r}")
    log(f"parity: passed; worst max|dlogit|/max|logit| = {worst!r} "
        f"(bound {PARITY_RTOL!r})")
    del dense, hobbit, params, model
    gc.collect()
    log(f"parity: {device_bytes(dev)}")


class _FiniteLogits:
    """Protocol proxy over a HobbitBackend that checks every logits row a
    live slot produces (prefill completions and decode steps)."""

    def __init__(self, backend):
        self._backend = backend
        self.model = backend.model
        self.rows = 0
        self.nonfinite = 0

    def __getattr__(self, name):
        return getattr(self._backend, name)

    def _see(self, rows):
        import numpy as np
        for lg in rows:
            self.rows += 1
            self.nonfinite += int(not np.isfinite(lg).all())

    def join_step(self):
        done = self._backend.join_step()
        self._see(done.values())
        return done

    def step(self, tokens):
        live = self._backend.engine.active.copy()
        lg = self._backend.step(tokens)
        self._see(lg[live])
        return lg


def mixed_phase(dev, seed: int, arch: str, layers: int, *, hi_slots: int,
                lo_slots: int, max_batch: int = 2,
                prompt_lens=(64, 256, 128, 192), new_tokens: int = 16,
                page: int = 64) -> None:
    import jax
    import numpy as np

    from repro.core import EngineConfig
    from repro.kernels import ops as kops
    from repro.models import build_model
    from repro.serving.api import BackendConfig, make_backend
    from repro.serving.batching import BatchingServer, Request

    cfg = model_config(arch, layers)
    tag = f"mixed {arch}"
    n_experts = cfg.num_layers * cfg.moe.num_experts
    check(hi_slots < n_experts and lo_slots < n_experts,
          f"{tag}: the pools must hold fewer experts than the model has")
    t0 = time.perf_counter()
    model = build_model(cfg)
    params = init_params(model, seed + 1)
    kops.reset_dispatch_counts()
    backend = _FiniteLogits(make_backend(BackendConfig(
        kind="hobbit", paged=True, page_size=page, prefill_chunk=page,
        engine=EngineConfig(hi_slots=hi_slots, lo_slots=lo_slots)),
        model, params))
    log(f"{tag}: {layers} layers x {cfg.moe.num_experts} experts, "
        f"hi_slots={hi_slots} lo_slots={lo_slots} max_batch={max_batch}; "
        f"setup {time.perf_counter() - t0:.3f} s; {device_bytes(dev)}")

    rng = np.random.default_rng(seed)
    srv = BatchingServer(backend, max_batch=max_batch,
                         max_len=max(prompt_lens) + new_tokens + 8)
    for rid, n in enumerate(prompt_lens):
        srv.submit(Request(rid=rid, max_new_tokens=new_tokens,
                           prompt=rng.integers(0, cfg.vocab_size, n)))
    t0 = time.perf_counter()
    srv.run()
    wall = time.perf_counter() - t0
    srv.close()
    s = srv.stats()
    eng = s["backend"]
    log(f"{tag}: served {s['requests']} requests in {wall:.3f} s "
        f"(compile included); decode_tok_s={s['decode_tok_s']!r} "
        f"mean_prefill_s={s['mean_prefill_s']!r} "
        f"mean_load_stall_s={s['mean_load_stall_s']!r} (smoke figures)")
    log(f"{tag}: loads_hi={eng['loads_hi']} loads_lo={eng['loads_lo']} "
        f"skips={eng['skips']} hit_ratio={eng['cache']['hit_ratio']!r} "
        f"loaded_bytes={eng['loaded_bytes']}")
    log(f"{tag}: kernel_dispatch={json.dumps(eng['kernel_dispatch'])}")
    log(f"{tag}: {device_bytes(dev)}")

    check(s["requests"] == len(prompt_lens),
          f"{tag}: {s['requests']} of {len(prompt_lens)} requests completed")
    check(all(len(r.output) == new_tokens for r in srv.completed),
          f"{tag}: a request produced the wrong number of tokens")
    check(backend.rows > 0 and backend.nonfinite == 0,
          f"{tag}: {backend.nonfinite} of {backend.rows} logits rows "
          "were not finite")
    check(eng["loads_lo"] > 0, f"{tag}: no lo expert copy was loaded")
    kd = eng["kernel_dispatch"]
    for op in MAIN_PATH_KERNELS:
        check(kd.get(f"{op}.pallas", 0) > 0,
              f"{tag}: {op} never dispatched the compiled kernel")
        for impl in ("pallas_interpret", "xla"):
            check(kd.get(f"{op}.{impl}", 0) == 0,
                  f"{tag}: {op} dispatched {impl}")
    log(f"{tag}: passed")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=2,
                    help="depth of the Phi-MoE mixed-precision phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    if os.environ.get("REPRO_KERNEL_MODE"):
        fail("REPRO_KERNEL_MODE is set; the smoke must run the kernels the "
             "TPU dispatch picks by itself")
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is missing: no {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}; jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    for arch in ("phi-moe", "mixtral-8x7b"):
        kernel_phase(arch, args.seed)
    t0 = time.perf_counter()
    parity_phase(dev, args.seed)
    log(f"parity phase wall {time.perf_counter() - t0:.3f} s")
    for arch, layers, hi_slots, lo_slots in (
            ("phi-moe", args.layers, 8, 16), ("mixtral-8x7b", 1, 4, 6)):
        t0 = time.perf_counter()
        mixed_phase(dev, args.seed, arch, layers, hi_slots=hi_slots,
                    lo_slots=lo_slots)
        log(f"mixed {arch} phase wall {time.perf_counter() - t0:.3f} s")
    cached = (sum(1 for p in pathlib.Path(cache_dir).rglob("*") if p.is_file())
              if pathlib.Path(cache_dir).is_dir() else 0)
    log(f"compile cache {cache_dir}: {cached} files")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
