"""Hot-path entry-point registry for the trace-time jaxpr auditor.

Every jitted function on the serving hot path is declared here once, with
tiny abstract input shapes (`jax.ShapeDtypeStruct` — nothing is executed,
only traced), the donation the production registration declares, the shapes
a dense pool gather would materialize, and the steady-state shape set the
variant-budget rule counts compile signatures over.  The auditor
(`tools/analysis/jaxpr_audit.py`) traces each entry under both
``REPRO_KERNEL_MODE`` values and applies the five hot-path rules.

Registry conventions (mirroring the PR-6 checkers):

* each entry names its production ``target`` as ``"module:Qual.name"``; a
  target that no longer resolves fires ``config-drift`` instead of crashing;
* a trailing ``# audit: ignore[rule, ...]`` on the ``entry(`` line
  suppresses the named rules for that entry (bare ``ignore`` matches all);
* donation tuples come from the same constants/sites production registers
  (``Model.PAGED_DECODE_DONATE``, ``OffloadEngine._scatter_fn``'s jit, the
  ``_copy_page`` module jit), so the donation-honored rule audits the real
  declaration, not a copy.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
import re
from typing import Any, Callable, List, Optional, Tuple

AUDIT_SUPPRESS_RE = re.compile(r"#\s*audit:\s*ignore(?:\[(?P<names>[^\]]*)\])?")

XLA = "xla"
PALLAS = "pallas"
BOTH_MODES = (XLA, PALLAS)


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One registered hot-path jit and the contract the auditor checks."""

    name: str                    # short id, e.g. "engine.grouped_ffn"
    target: str                  # "repro.core.engine:OffloadEngine._grouped_ffn"
    fn: Callable[..., Any]       # the callable to trace (raw or already jitted)
    args: Tuple[Any, ...]        # primary abstract build (ShapeDtypeStructs ok)
    donate: Tuple[int, ...] = ()         # donated argnums, as production declares
    pool_args: Tuple[int, ...] = ()      # which donated args are pool buffers
    dense_shapes: Tuple[Tuple[int, ...], ...] = ()   # forbidden intermediates
    dense_oracle_mode: Optional[str] = XLA   # mode REQUIRED to show the dense
    # shape (the self-validating positive control inherited from the PR-7
    # bench scan: if the oracle stops gathering, the check is broken, not
    # passing); None disables the control
    activation_dtype: Optional[str] = None   # "bfloat16" arms the dtype rule
    quant_dtypes: Tuple[str, ...] = ()       # dtypes that may only widen
    # inside fused (pallas_call) kernels when mode == "pallas"
    variant_builds: Tuple[Tuple[Any, ...], ...] = ()   # steady-state shape set
    variant_budget: int = 1      # distinct compile signatures the set may cost
    modes: Tuple[str, ...] = BOTH_MODES
    ignore: Tuple[str, ...] = ()     # rules suppressed via "# audit: ignore[...]"
    bare_ignore: bool = False
    srcfile: str = ""
    lineno: int = 0

    def builds(self) -> Tuple[Tuple[Any, ...], ...]:
        return self.variant_builds if self.variant_builds else (self.args,)

    def suppresses(self, rule: str) -> bool:
        return self.bare_ignore or rule in self.ignore


def entry(**kw: Any) -> EntryPoint:
    """EntryPoint factory that records its own call site, so a trailing
    ``# audit: ignore[rule]`` comment on the ``entry(`` line suppresses the
    named rules — same line-anchored convention as ``# analysis: ignore``."""
    frame = inspect.currentframe()
    caller = frame.f_back if frame is not None else None
    srcfile, lineno = "", 0
    ignore: Tuple[str, ...] = ()
    bare = False
    if caller is not None:
        srcfile = caller.f_code.co_filename
        lineno = caller.f_lineno
        try:
            with open(srcfile) as fh:
                line = fh.read().splitlines()[lineno - 1]
            m = AUDIT_SUPPRESS_RE.search(line)
            if m:
                names = m.group("names")
                if names:
                    ignore = tuple(n.strip() for n in names.split(","))
                else:
                    bare = True
        except (OSError, IndexError):
            pass
    return EntryPoint(srcfile=srcfile, lineno=lineno, ignore=ignore,
                      bare_ignore=bare, **kw)


def resolve_target(target: str) -> Any:
    """Resolve ``"module:attr.path"`` to the live object; raises on drift."""
    mod_name, _, attr_path = target.partition(":")
    obj: Any = importlib.import_module(mod_name)
    for part in attr_path.split("."):
        obj = getattr(obj, part)
    return obj


# --------------------------------------------------------------------------
# the real registry
# --------------------------------------------------------------------------
def _smoke() -> Tuple[Any, Any, Any]:
    """Tiny bfloat16 mixtral smoke model + grouped/paged engine, built once
    per audit run.  bfloat16 (not the test suites' float32) so the dtype-
    policy rule sees the production activation width; nothing is executed,
    so numerics never matter."""
    import dataclasses as dc

    import jax

    from repro.configs import get_config, smoke_variant
    from repro.core import EngineConfig, OffloadEngine
    from repro.models import build_model

    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=2, d_model=64,
                        vocab=128)
    cfg = dc.replace(cfg, dtype="bfloat16",
                     moe=dc.replace(cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = OffloadEngine(m, params, EngineConfig(
        hi_slots=8, lo_slots=4, grouped=True, paged_kv=True, kv_page_size=4,
        kv_pages=32, link_gbps=8.0))
    eng.start_batch(2, 24)
    return m, params, eng


def build_registry() -> Tuple[List[EntryPoint], List[Tuple[str, str, str]]]:
    """Build the hot-path registry against the live tree.

    Returns ``(entries, drift)`` where ``drift`` lists
    ``(entry_name, target, error)`` for every registered entry point whose
    production target no longer resolves — the auditor turns those into
    ``config-drift`` findings instead of crashing mid-trace."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    S = jax.ShapeDtypeStruct
    entries: List[EntryPoint] = []
    drift: List[Tuple[str, str, str]] = []

    def guard(name: str, target: str,
              builder: Callable[[], EntryPoint]) -> None:
        try:
            resolve_target(target)
            entries.append(builder())
        except Exception as e:  # noqa: BLE001 — drift must never crash the audit
            drift.append((name, target, f"{type(e).__name__}: {e}"))

    m, params, eng = _smoke()
    try:
        cfg = m.cfg
        b, k = 2, cfg.moe.top_k
        d, f = cfg.d_model, cfg.moe.d_ff_expert
        act = jnp.bfloat16
        li = eng.moe_layers[0]
        kp_shape = eng.kv_pool.k[li].shape          # (P, psz, Hkv, hd)
        npages, psz, hkv, hd = kp_shape
        table_shape = tuple(eng.kv_pool.table_device().shape)   # (B, maxp)
        maxp = table_shape[1]
        dense = (b, maxp * psz, hkv, hd)
        layer_p = eng.layer_params[li]
        wi_cols = eng.pool_hi["wi"].shape[-1]

        # ---- the three Pallas-wrapper ops (kernels/ops.py) ----
        ops_pfd_args = (S((b, cfg.num_heads, hd), act), S(kp_shape, act),
                        S(kp_shape, act), S(table_shape, jnp.int32),
                        S((b,), jnp.int32))
        guard("ops.paged_flash_decode",
              "repro.kernels.ops:paged_flash_decode",
              lambda: entry(
                  name="ops.paged_flash_decode",
                  target="repro.kernels.ops:paged_flash_decode",
                  fn=lambda q, pk, pv, t, ln: kops.paged_flash_decode(
                      q, pk, pv, t, ln),
                  args=ops_pfd_args,
                  dense_shapes=(dense,)))

        gsz = eng.ecfg.group_size
        bits = eng.ecfg.lo_bits
        pp = b * k
        # combine contracts x (P, F) against the second lo GEMM's quantized
        # wo — row shapes come straight off the engine's lo pool so the
        # declared tiny shapes track the production packing exactly
        wo_data_row = eng.pool_lo["wo_data"].shape[1:]
        wo_scale_row = eng.pool_lo["wo_scale"].shape[1:]
        gdc_args = (S((pp, f), act),
                    S((pp, *wo_data_row), jnp.int8),
                    S((pp, *wo_scale_row), jnp.float32),
                    S((pp,), jnp.int32), S((pp,), jnp.float32))
        guard("ops.grouped_dequant_combine",
              "repro.kernels.ops:grouped_dequant_combine",
              lambda: entry(
                  name="ops.grouped_dequant_combine",
                  target="repro.kernels.ops:grouped_dequant_combine",
                  fn=lambda x, dq, sc, rows, w: kops.grouped_dequant_combine(
                      x, dq, sc, rows, w, bits=bits, group_size=gsz,
                      num_rows=b),
                  args=gdc_args,
                  activation_dtype="bfloat16",
                  quant_dtypes=("int8",)))

        e_experts = cfg.moe.num_experts
        guard("ops.gating_topk", "repro.kernels.ops:gating_topk",
              lambda: entry(
                  name="ops.gating_topk",
                  target="repro.kernels.ops:gating_topk",
                  fn=lambda x, gates: kops.gating_topk(x, gates, top_k=k),
                  args=(S((b, d), act), S((1, d, e_experts), jnp.float32)),
                  activation_dtype="bfloat16"))

        # ---- engine grouped decode step ----
        hi_pool = {n: S(a.shape, a.dtype) for n, a in eng.pool_hi.items()}
        lo_pool = {n: S(a.shape, a.dtype) for n, a in eng.pool_lo.items()}
        idx32 = S((pp,), jnp.int32)
        gffn_args = (hi_pool["wi"], hi_pool["wo"],
                     lo_pool["wi_data"], lo_pool["wi_scale"],
                     lo_pool["wo_data"], lo_pool["wo_scale"],
                     S((pp, *eng.pool_hi["wi"].shape[1:]), eng.dtype),
                     S((pp, *eng.pool_hi["wo"].shape[1:]), eng.dtype),
                     S((pp, *eng.pool_lo["wi_data"].shape[1:]), jnp.int8),
                     S((pp, *eng.pool_lo["wi_scale"].shape[1:]), jnp.float32),
                     S((pp, *eng.pool_lo["wo_data"].shape[1:]), jnp.int8),
                     S((pp, *eng.pool_lo["wo_scale"].shape[1:]), jnp.float32),
                     S((b, 1, d), act),
                     idx32, idx32, idx32, idx32, idx32, idx32,
                     S((b, k), jnp.float32), S((b, k), jnp.float32))
        guard("engine.grouped_ffn",
              "repro.core.engine:OffloadEngine._grouped_ffn",
              lambda: entry(
                  name="engine.grouped_ffn",
                  target="repro.core.engine:OffloadEngine._grouped_ffn",
                  fn=eng._grouped_ffn,
                  args=gffn_args,
                  activation_dtype="bfloat16",
                  quant_dtypes=("int8",)))

        attn_args = (layer_p, S((b, 1, d), act), S(kp_shape, act),
                     S(kp_shape, act), S(table_shape, jnp.int32),
                     S((b,), jnp.int32), S((b,), jnp.bool_))
        guard("engine.attn_paged",
              "repro.core.engine:OffloadEngine._attn_step_paged",
              lambda: entry(
                  name="engine.attn_paged",
                  target="repro.core.engine:OffloadEngine._attn_step_paged",
                  fn=eng._attn_step_paged,
                  args=attn_args,
                  donate=(2, 3), pool_args=(2, 3),
                  dense_shapes=(dense,)))

        # ---- StagingEngine's per-expert commit scatter (hi / lo pools) ----
        # The traced fns are the PRODUCTION jitted objects out of
        # eng._scatter_fn's cache — donation included.  The scatter is pure
        # index math with no kernel dispatch, so one mode suffices.
        hi_pools = [hi_pool["wi"], hi_pool["wo"]]
        hi_shapes = [eng.pool_hi["wi"].shape[1:], eng.pool_hi["wo"].shape[1:]]
        guard("engine.commit_scatter_hi",
              "repro.core.engine:OffloadEngine._scatter_fn",
              lambda: entry(
                  name="engine.commit_scatter_hi",
                  target="repro.core.engine:OffloadEngine._scatter_fn",
                  fn=eng._scatter_fn(2),
                  args=(hi_pools, S((1,), jnp.int32),
                        [S((1, *s), jnp.float32) for s in hi_shapes]),
                  donate=(0,), pool_args=(0,),
                  modes=(XLA,)))

        lo_names = ("wi_data", "wi_scale", "wo_data", "wo_scale")
        lo_pools = [lo_pool[n] for n in lo_names]
        lo_shapes = [eng.pool_lo[n].shape[1:] for n in lo_names]
        lo_dts = [jnp.int8, jnp.float32, jnp.int8, jnp.float32]
        guard("engine.commit_scatter_lo",
              "repro.core.engine:OffloadEngine._scatter_fn",
              lambda: entry(
                  name="engine.commit_scatter_lo",
                  target="repro.core.engine:OffloadEngine._scatter_fn",
                  fn=eng._scatter_fn(4),
                  args=(lo_pools, S((1,), jnp.int32),
                        [S((1, *s), dt) for s, dt in zip(lo_shapes, lo_dts)]),
                  donate=(0,), pool_args=(0,),
                  modes=(XLA,)))

        # ---- paged decode / prefill-chunk jits (model + serving tier) ----
        kpages = [S(eng.kv_pool.k[i].shape, act)
                  for i in range(len(eng.kv_pool.k))]
        vpages = [S(eng.kv_pool.v[i].shape, act)
                  for i in range(len(eng.kv_pool.v))]
        decode_args = (params, kpages, vpages, S(table_shape, jnp.int32),
                       S((b, 1), jnp.int32), S((b,), jnp.int32),
                       S((b,), jnp.bool_))
        guard("model.decode_step_paged",
              "repro.models.model:Model.decode_step_paged",
              lambda: entry(
                  name="model.decode_step_paged",
                  target="repro.models.model:Model.decode_step_paged",
                  fn=m.decode_step_paged,
                  args=decode_args,
                  donate=type(m).PAGED_DECODE_DONATE,
                  pool_args=type(m).PAGED_DECODE_DONATE,
                  dense_shapes=(dense,)))

        chunk = 4
        prefill_args = (params, kpages, vpages, S(table_shape, jnp.int32),
                        S((b, chunk), jnp.int32), S((b,), jnp.int32),
                        S((b,), jnp.int32), S((b,), jnp.int32))
        guard("model.prefill_chunk_paged",
              "repro.models.model:Model.prefill_chunk_paged",
              lambda: entry(
                  name="model.prefill_chunk_paged",
                  target="repro.models.model:Model.prefill_chunk_paged",
                  fn=m.prefill_chunk_paged,
                  args=prefill_args,
                  donate=type(m).PAGED_PREFILL_DONATE,
                  pool_args=type(m).PAGED_PREFILL_DONATE))

        # ---- pool page-copy jit (models/kv_pages.py) ----
        from repro.models import kv_pages as kvp
        guard("kv.copy_page", "repro.models.kv_pages:_copy_page",
              lambda: entry(
                  name="kv.copy_page",
                  target="repro.models.kv_pages:_copy_page",
                  fn=kvp._copy_page,
                  args=(S(kp_shape, act), S((), jnp.int32), S((), jnp.int32)),
                  donate=(0,), pool_args=(0,),
                  modes=(XLA,)))
    finally:
        eng.close()
    return entries, drift
