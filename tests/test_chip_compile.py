"""Compile-only checks at published widths for one TPU v5e chip.

The main-path Pallas kernels and the engine's jitted ``grouped_ffn`` are
lowered and compiled for a described (not attached) v5e chip, at
Mixtral-8x7B and Phi-MoE widths.  Each compile must contain the Mosaic
kernel (``tpu_custom_call``) and fit 16 GB by ``memory_analysis()`` — the
tiling and VMEM refusals that interpret mode cannot see surface here,
without a chip.

The topology is described inside a module-scoped fixture, never at import:
only the process that runs these tests may load the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import EngineConfig, OffloadEngine
from repro.kernels import ops as kops

HBM_BYTES = 16e9
WIDTHS = ("phi-moe", "mixtral-8x7b")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 slice; the persistent compile cache
    is off meanwhile (an entry compiled for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture
def compiled_dispatch(monkeypatch):
    """Steer ``mode="auto"`` to the compiled kernels, as on a TPU host."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return compiled.as_text(), total


def _kernel_case(kernel, arch, sds):
    """(fn, abstract args) for one kernel through its ops wrapper at the
    decode shapes the engine feeds it (batch 2, P = batch * top_k)."""
    cfg = get_config(arch)
    mc = cfg.moe
    d, f, e, k = cfg.d_model, mc.d_ff_expert, mc.num_experts, mc.top_k
    b, p, g, bits = 2, 2 * k, 64, 4
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    bf, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
    if kernel == "gating_topk":
        return (lambda x, w: kops.gating_topk(x, w, top_k=k),
                (sds((b, d), bf), sds((1, d, e), f32)))
    if kernel == "stacked_gating":
        return (kops.stacked_gating, (sds((b, d), bf), sds((2, d, e), f32)))
    if kernel == "grouped_dequant_matmul":
        return (lambda x, q, s: kops.grouped_dequant_matmul(
                    x, q, s, bits=bits, group_size=g),
                (sds((p, d), bf), sds((p, d // 2, 2 * f), i8),
                 sds((p, d // g, 2 * f), f32)))
    if kernel == "grouped_dequant_combine":
        return (lambda x, q, s, r, w: kops.grouped_dequant_combine(
                    x, q, s, r, w, bits=bits, group_size=g, num_rows=b),
                (sds((p, f), bf), sds((p, f // 2, d), i8),
                 sds((p, f // g, d), f32), sds((p,), i32), sds((p,), f32)))
    assert kernel == "paged_flash_decode"
    pages = sds((32, 64, hkv, hd), bf)
    return (lambda q, kp, vp, t, n: kops.paged_flash_decode(
                q, kp, vp, t, n, scale=hd ** -0.5),
            (sds((b, hq, hd), bf), pages, pages, sds((b, 8), i32),
             sds((b,), i32)))


@pytest.mark.parametrize("arch", WIDTHS)
@pytest.mark.parametrize("kernel", ["gating_topk", "stacked_gating",
                                    "grouped_dequant_matmul",
                                    "grouped_dequant_combine",
                                    "paged_flash_decode"])
def test_kernel_compiles_for_v5e(one_chip, compiled_dispatch, kernel, arch):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(kernel, arch, sds)
    hlo, total = _compile(fn, *args)
    assert "tpu_custom_call" in hlo, f"{kernel} compiled without its kernel"
    assert total <= HBM_BYTES, total


def test_grouped_ffn_compiles_for_v5e(one_chip, compiled_dispatch):
    """The engine's per-layer expert step at the parity shape of
    chip_smoke.py: Phi-MoE widths, every expert of one layer in the hi pool,
    batch 2.  Both lo kernels must be in the program and the whole step —
    pools, overflow buffers and the pool concatenation — must fit."""
    cfg = get_config("phi-moe")
    ecfg = EngineConfig(hi_slots=cfg.moe.num_experts, lo_slots=1)
    # the step needs only the config; a real engine would allocate the
    # model's experts on the host
    eng = object.__new__(OffloadEngine)
    eng.cfg, eng.ecfg = cfg, ecfg
    d, f, k = cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.top_k
    b, g, pack = 2, ecfg.group_size, 2
    p = b * k
    bf, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def lo(n, kk, nn):
        return [sds((n, kk // pack, nn), i8), sds((n, kk // g, nn), f32)]

    args = [sds((ecfg.hi_slots, d, 2 * f), bf), sds((ecfg.hi_slots, f, d), bf),
            *lo(ecfg.lo_slots, d, 2 * f), *lo(ecfg.lo_slots, f, d),
            sds((p, d, 2 * f), bf), sds((p, f, d), bf),
            *lo(p, d, 2 * f), *lo(p, f, d), sds((b, 1, d), bf),
            *[sds((p,), i32)] * 6, sds((b, k), f32), sds((b, k), f32)]
    hlo, total = _compile(eng._grouped_ffn, *args)
    assert hlo.count("tpu_custom_call") >= 2, "lo kernels missing"
    assert total <= HBM_BYTES, total
