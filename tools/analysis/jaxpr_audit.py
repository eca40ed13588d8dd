"""Trace-time jaxpr auditor for the serving hot path.

Traces every registered entry point (tools/analysis/entrypoints.py) with
tiny abstract inputs via ``jax.make_jaxpr`` / ``jax.jit(...).lower`` under
both ``REPRO_KERNEL_MODE`` values and applies five rules:

* **no-host-sync** — no callback / infeed / outfeed / device-transfer
  primitive anywhere inside a traced region;
* **donation-honored** — every argument the production jit declares donated
  is actually recorded as input/output-aliased by the lowering (JAX drops
  unusable donations with only a warning; the auditor turns that warning,
  and a lowering with no aliasing at all, into a violation);
* **no-dense-gather** — no intermediate with a declared forbidden
  ``(B, pages*page_size, ...)`` dense-pool shape on decode paths, with the
  PR-7 self-validating positive control: the declared oracle mode (the XLA
  reference path) MUST materialize the dense shape, otherwise the check
  itself is broken and the auditor says so instead of passing;
* **dtype-policy** — no silent f32 upcast of the declared bfloat16
  activations: a ``dot_general`` that runs in f32 on operands upcast from
  bf16 and whose result is immediately downcast back to bf16 bought nothing
  but bandwidth (the GEMM should have run in bf16); dots with a quantized
  (int8) ancestor are the fused-dequant contract and exempt, as are
  f32 results that remain f32 (deliberate accumulations, logits).  Under
  ``pallas`` mode, quantized operands may only widen inside ``pallas_call``
  kernels;
* **variant-budget** — the declared steady-state shape set costs exactly
  the declared number of distinct compile signatures (the static twin of
  tests/test_recompile_guard.py).

Findings render as ``entrypoint: [rule] primitive @ eqn — message`` with the
offending jaxpr slice, and ``config-drift`` fires when a registered entry
point disappears — the same conventions as the PR-6 AST checkers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import pathlib
import warnings
from typing import (Any, Dict, Iterable, Iterator, List, Optional, Sequence,
                    Set, Tuple)

from tools.analysis.entrypoints import (BOTH_MODES, EntryPoint,
                                        build_registry, resolve_target)

RULES = ("no-host-sync", "donation-honored", "no-dense-gather",
         "dtype-policy", "variant-budget")

# primitives that force a host round-trip or device transfer inside a trace
_HOST_SYNC_SUBSTR = ("callback",)     # pure_callback / io_callback / debug_callback
_HOST_SYNC_EXACT = {"infeed", "outfeed", "device_put"}

# dataflow-transparent primitives the dtype rule walks through backwards
_TRANSPARENT = {
    "convert_element_type", "reshape", "transpose", "broadcast_in_dim",
    "squeeze", "slice", "dynamic_slice", "gather", "concatenate", "pad",
    "rev", "add", "sub", "mul", "div", "max", "min", "neg", "select_n",
    "clamp", "stop_gradient", "copy",
}
# elementwise-ish primitives the downcast search walks forwards through
_FORWARD = _TRANSPARENT | {"exp", "log", "tanh", "logistic", "rsqrt", "sqrt",
                           "integer_pow", "pow", "erf", "reduce_sum",
                           "reduce_max"}

_QUANT_DTYPES_DEFAULT = ("int8", "uint8", "int4", "uint4")


@dataclasses.dataclass
class AuditFinding:
    """One audited-rule violation, printable as
    ``entrypoint: [rule] primitive @ eqn — message``."""
    entrypoint: str
    rule: str
    primitive: str = "-"
    eqn: str = "-"
    message: str = ""
    jaxpr_slice: str = ""

    def render(self) -> str:
        return (f"{self.entrypoint}: [{self.rule}] {self.primitive} "
                f"@ eqn {self.eqn} — {self.message}")


@contextlib.contextmanager
def _kernel_mode(mode: str) -> Iterator[None]:
    """Pin REPRO_KERNEL_MODE for the duration of one trace.  kernels/ops.py
    resolves mode="auto" from the environment AT TRACE TIME, so this is the
    exact mechanism production uses to pick a dispatch tier."""
    prev = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNEL_MODE", None)
        else:
            os.environ["REPRO_KERNEL_MODE"] = prev


def _trace(entry: EntryPoint, mode: str) -> Any:
    import jax
    fn = entry.fn
    # make_jaxpr rides the jit trace cache, keyed on (function identity,
    # avals) — NOT on REPRO_KERNEL_MODE, which ops._resolve reads from the
    # environment at trace time.  A fresh wrapper per trace forces a genuine
    # re-trace under the pinned mode instead of returning the other mode's
    # cached jaxpr.
    with _kernel_mode(mode):
        return jax.make_jaxpr(lambda *a: fn(*a))(*entry.args)


def _subjaxprs(eqn: Any) -> List[Tuple[Any, bool]]:
    """(inner_jaxpr, entered_pallas) pairs reachable from one eqn's params —
    handles ClosedJaxpr params (pjit, scan, ...) and the raw Jaxpr that
    ``pallas_call`` carries, nested arbitrarily in lists/tuples."""
    from jax.extend import core as jex_core
    is_pallas = eqn.primitive.name == "pallas_call"
    out: List[Tuple[Any, bool]] = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out.append((inner, is_pallas))
            elif isinstance(v, jex_core.Jaxpr):
                out.append((v, is_pallas))
    return out


def _iter_eqns(jaxpr: Any, path: Tuple[int, ...] = (),
               in_pallas: bool = False) -> Iterator[Tuple[str, Any, bool]]:
    """Yield ``("0/3/1", eqn, inside_pallas_kernel)`` over all regions."""
    for i, eqn in enumerate(jaxpr.eqns):
        loc = path + (i,)
        yield "/".join(map(str, loc)), eqn, in_pallas
        for inner, entered in _subjaxprs(eqn):
            yield from _iter_eqns(inner, loc, in_pallas or entered)


def _slice(eqn: Any) -> str:
    txt = str(eqn).replace("\n", " ")
    return txt if len(txt) <= 220 else txt[:217] + "..."


# --------------------------------------------------------------------------
# rule: no-host-sync
# --------------------------------------------------------------------------
def check_host_sync(entry: EntryPoint, jaxpr: Any,
                    mode: str) -> List[AuditFinding]:
    out = []
    for loc, eqn, _ in _iter_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in _HOST_SYNC_EXACT or any(s in name for s in _HOST_SYNC_SUBSTR):
            out.append(AuditFinding(
                entry.name, "no-host-sync", name, loc,
                f"host-sync/transfer primitive inside the traced region "
                f"(mode={mode}); the decode hot path must stay device-only",
                _slice(eqn)))
    return out


# --------------------------------------------------------------------------
# rule: no-dense-gather
# --------------------------------------------------------------------------
def _shapes(jaxpr: Any) -> Dict[Tuple[int, ...], Tuple[str, str]]:
    """All intermediate output shapes -> (first primitive, eqn loc)."""
    found: Dict[Tuple[int, ...], Tuple[str, str]] = {}
    for loc, eqn, in_pallas in _iter_eqns(jaxpr.jaxpr):
        if in_pallas:
            continue        # kernel-interior blocks are tile-shaped views
        for v in eqn.outvars:
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None and tuple(shape) not in found:
                found[tuple(shape)] = (eqn.primitive.name, loc)
    return found


def check_dense_gather(entry: EntryPoint, jaxpr: Any, mode: str,
                       oracle_seen: Optional[Set[Tuple[int, ...]]] = None
                       ) -> List[AuditFinding]:
    """Forbidden dense shapes must be absent outside the oracle mode; in the
    oracle mode their PRESENCE is required (self-validating control)."""
    out = []
    found = _shapes(jaxpr)
    for shape in entry.dense_shapes:
        if mode == entry.dense_oracle_mode:
            if oracle_seen is not None and shape in found:
                oracle_seen.add(shape)
            continue
        if shape in found:
            prim, loc = found[shape]
            out.append(AuditFinding(
                entry.name, "no-dense-gather", prim, loc,
                f"intermediate with dense pool-gather shape {shape} under "
                f"mode={mode}; the kernel tier exists to keep this "
                f"materialization off the decode path"))
    return out


def oracle_control_findings(entry: EntryPoint,
                            oracle_seen: Set[Tuple[int, ...]],
                            oracle_ran: bool) -> List[AuditFinding]:
    """PR-7's positive control: the reference mode must still gather dense,
    or the no-dense-gather check is vacuous and reports itself broken."""
    if not entry.dense_shapes or entry.dense_oracle_mode is None:
        return []
    if not oracle_ran:
        return []
    out = []
    for shape in entry.dense_shapes:
        if shape not in oracle_seen:
            out.append(AuditFinding(
                entry.name, "no-dense-gather", "-", "-",
                f"positive control failed: oracle mode "
                f"'{entry.dense_oracle_mode}' no longer materializes dense "
                f"shape {shape}, so absence under the kernel tier proves "
                f"nothing — update the entry's declared dense_shapes"))
    return out


# --------------------------------------------------------------------------
# rule: dtype-policy
# --------------------------------------------------------------------------
class _FlatGraph:
    """The traced program flattened across pjit/scan sub-regions: every eqn
    at every depth, with sub-jaxpr boundary variables aliased to their
    call-site operands so dataflow walks cross region boundaries.
    ``pallas_call`` interiors are kept but marked (the fused-kernel
    exemption).  Control-flow primitives whose operand lists don't line up
    1:1 (cond, while) simply break the chain — conservative, never a false
    positive."""

    def __init__(self, jaxpr: Any) -> None:
        self.alias: Dict[int, Any] = {}
        self.producer: Dict[int, Tuple[Any, str, bool]] = {}
        self.consumers: Dict[int, List[Tuple[Any, str, bool]]] = {}
        self.eqns: List[Tuple[str, Any, bool]] = []
        self._walk(jaxpr.jaxpr, (), False)

    def _link(self, inner_vars: Any, outer_vars: Any) -> None:
        from jax.extend import core as jex_core
        if len(inner_vars) != len(outer_vars):
            return
        for iv, ov in zip(inner_vars, outer_vars):
            if isinstance(iv, jex_core.Var) and isinstance(ov, jex_core.Var):
                self.alias[id(iv)] = ov

    def canon(self, var: Any) -> Any:
        seen = set()
        while id(var) in self.alias and id(var) not in seen:
            seen.add(id(var))
            var = self.alias[id(var)]
        return var

    def _walk(self, jaxpr: Any, path: Tuple[int, ...],
              in_pallas: bool) -> None:
        for i, eqn in enumerate(jaxpr.eqns):
            loc = "/".join(map(str, path + (i,)))
            self.eqns.append((loc, eqn, in_pallas))
            for inner, entered in _subjaxprs(eqn):
                inner_jaxpr = getattr(inner, "jaxpr", inner)
                if eqn.primitive.name in ("pjit", "closed_call", "core_call",
                                          "remat", "checkpoint"):
                    self._link(inner_jaxpr.invars, eqn.invars)
                    self._link(eqn.outvars, inner_jaxpr.outvars)
                self._walk(inner_jaxpr, path + (i,), in_pallas or entered)
        # producer/consumer maps on canonical vars (second pass so aliases
        # registered above resolve)
        if not path:
            for loc, eqn, pl in self.eqns:
                for v in eqn.outvars:
                    self.producer.setdefault(id(self.canon(v)), (eqn, loc, pl))
                for v in eqn.invars:
                    if hasattr(v, "aval"):
                        self.consumers.setdefault(
                            id(self.canon(v)), []).append((eqn, loc, pl))


def _dtype_of(v: Any) -> str:
    return str(getattr(getattr(v, "aval", None), "dtype", ""))


def _crossed_bf16_upcast(g: _FlatGraph, var: Any,
                         limit: int = 400) -> bool:
    """Backward walk through transparent ops: did this value pass a
    bf16 -> f32 convert?"""
    stack, seen = [g.canon(var)], set()
    while stack and len(seen) < limit:
        v = stack.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        prod = g.producer.get(id(v))
        if prod is None:
            continue
        eqn, _, _ = prod
        name = eqn.primitive.name
        if name == "convert_element_type":
            if (_dtype_of(eqn.invars[0]) == "bfloat16"
                    and _dtype_of(eqn.outvars[0]) == "float32"):
                return True
        if name in _TRANSPARENT or name == "pjit":
            stack.extend(g.canon(iv) for iv in eqn.invars
                         if hasattr(iv, "aval"))
    return False


def _has_quant_ancestor(g: _FlatGraph, var: Any, quant_dtypes: Sequence[str],
                        limit: int = 800) -> bool:
    """Backward walk through ANY primitive: does an int8-family value feed
    this operand?  (The fused-dequant exemption: a GEMM against dequantized
    weights legitimately runs in f32.)"""
    stack, seen = [g.canon(var)], set()
    while stack and len(seen) < limit:
        v = stack.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        if _dtype_of(v) in quant_dtypes:
            return True
        prod = g.producer.get(id(v))
        if prod is None:
            continue
        eqn, _, _ = prod
        stack.extend(g.canon(iv) for iv in eqn.invars if hasattr(iv, "aval"))
    return False


def _downcast_downstream(g: _FlatGraph, var: Any,
                         limit: int = 400) -> bool:
    """Forward walk through elementwise ops: is this f32 value converted
    back down to bf16?  (If it stays f32 — logits, accumulators — the wide
    compute was the contract, not a silent upcast.)"""
    stack, seen = [g.canon(var)], set()
    while stack and len(seen) < limit:
        v = stack.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        for eqn, _, _ in g.consumers.get(id(v), ()):
            name = eqn.primitive.name
            if (name == "convert_element_type"
                    and _dtype_of(eqn.outvars[0]) == "bfloat16"):
                return True
            if name in _FORWARD or name == "pjit":
                stack.extend(g.canon(ov) for ov in eqn.outvars)
    return False


def check_dtype_policy(entry: EntryPoint, jaxpr: Any,
                       mode: str) -> List[AuditFinding]:
    out: List[AuditFinding] = []
    quant = tuple(entry.quant_dtypes) or ()
    need_act = entry.activation_dtype == "bfloat16"
    need_quant = bool(quant) and mode == "pallas"
    if not (need_act or need_quant):
        return []
    g = _FlatGraph(jaxpr)

    if need_act:
        for loc, eqn, in_pallas in g.eqns:
            if in_pallas or eqn.primitive.name != "dot_general":
                continue
            if _dtype_of(eqn.outvars[0]) != "float32":
                continue
            upcast = any(_crossed_bf16_upcast(g, v) for v in eqn.invars
                         if hasattr(v, "aval"))
            if not upcast:
                continue
            if any(_has_quant_ancestor(g, v, _QUANT_DTYPES_DEFAULT)
                   for v in eqn.invars if hasattr(v, "aval")):
                continue        # fused-dequant contract: wide GEMM is the point
            if _downcast_downstream(g, eqn.outvars[0]):
                out.append(AuditFinding(
                    entry.name, "dtype-policy", "dot_general", loc,
                    f"silent f32 upcast (mode={mode}): a GEMM runs in f32 on "
                    f"operands upcast from bfloat16 and its result is "
                    f"immediately downcast back — run it in bf16 (or keep "
                    f"the f32 result if wide accumulation was intended)",
                    _slice(eqn)))

    if need_quant:
        for loc, eqn, in_pallas in g.eqns:
            if in_pallas or eqn.primitive.name != "convert_element_type":
                continue
            src = _dtype_of(eqn.invars[0])
            dst = _dtype_of(eqn.outvars[0])
            if src in quant and dst.startswith("float"):
                out.append(AuditFinding(
                    entry.name, "dtype-policy", "convert_element_type", loc,
                    f"quantized operand widens {src} -> {dst} outside a "
                    f"fused pallas kernel under mode={mode}; dequantization "
                    f"must stay inside the kernel tier",
                    _slice(eqn)))
    return out


# --------------------------------------------------------------------------
# rule: donation-honored
# --------------------------------------------------------------------------
def check_donation(entry: EntryPoint, mode: str) -> List[AuditFinding]:
    import jax
    if not entry.donate:
        return []
    fn = entry.fn
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, donate_argnums=entry.donate)
    out: List[AuditFinding] = []
    with _kernel_mode(mode):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            lowered = jitted.lower(*entry.args)
        dropped = [str(w.message) for w in caught
                   if "donated buffers were not usable" in str(w.message)]
    if dropped:
        detail = dropped[0].splitlines()[0]
        out.append(AuditFinding(
            entry.name, "donation-honored", "-", "-",
            f"declared donation dropped at lowering (mode={mode}): {detail} "
            f"— the annotated buffer is never aliased to an output, so the "
            f"pool is silently double-buffered"))
        return out
    n_aliased = lowered.as_text().count("tf.aliasing_output")
    if n_aliased == 0:
        out.append(AuditFinding(
            entry.name, "donation-honored", "-", "-",
            f"lowering records no input/output aliasing (mode={mode}) "
            f"despite donate_argnums={entry.donate}; donation is annotated "
            f"but not honored"))
    return out


# --------------------------------------------------------------------------
# rule: variant-budget
# --------------------------------------------------------------------------
def _signature(args: Any) -> Tuple[Any, ...]:
    import jax
    return tuple((tuple(x.shape), str(x.dtype))
                 for x in jax.tree_util.tree_leaves(args))


def check_variant_budget(entry: EntryPoint) -> List[AuditFinding]:
    sigs = {_signature(b) for b in entry.builds()}
    if len(sigs) == entry.variant_budget:
        return []
    return [AuditFinding(
        entry.name, "variant-budget", "-", "-",
        f"the declared steady-state shape set compiles {len(sigs)} distinct "
        f"variant(s) but the entry budgets exactly {entry.variant_budget}; "
        f"either a padding/canonicalization step regressed (recompiles at "
        f"serve time) or the declared budget is stale")]


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def audit_entry(entry: EntryPoint,
                modes: Optional[Sequence[str]] = None) -> List[AuditFinding]:
    """Run every applicable rule for one entry across its kernel modes."""
    findings: List[AuditFinding] = []
    run_modes = [m for m in (modes or entry.modes) if m in entry.modes]
    # the oracle mode must run for the dense positive control even when the
    # caller restricts modes (e.g. CI's REPRO_KERNEL_MODE=pallas pass)
    if (entry.dense_shapes and entry.dense_oracle_mode
            and entry.dense_oracle_mode in entry.modes
            and entry.dense_oracle_mode not in run_modes):
        run_modes = [entry.dense_oracle_mode] + run_modes
    oracle_seen: Set[Tuple[int, ...]] = set()
    oracle_ran = False
    for mode in run_modes:
        jaxpr = _trace(entry, mode)
        findings += check_host_sync(entry, jaxpr, mode)
        findings += check_dense_gather(entry, jaxpr, mode, oracle_seen)
        if mode == entry.dense_oracle_mode:
            oracle_ran = True
        findings += check_dtype_policy(entry, jaxpr, mode)
        findings += check_donation(entry, mode)
    findings += oracle_control_findings(entry, oracle_seen, oracle_ran)
    findings += check_variant_budget(entry)
    return [f for f in findings if not entry.suppresses(f.rule)]


def run_audit(registry: Optional[Sequence[EntryPoint]] = None,
              modes: Optional[Sequence[str]] = None,
              drift: Optional[Sequence[Tuple[str, str, str]]] = None
              ) -> List[AuditFinding]:
    """Audit a registry (default: the real one).  ``modes`` restricts the
    kernel modes traced (None = each entry's declared modes)."""
    if registry is None:
        registry, drift = build_registry()
    findings: List[AuditFinding] = []
    for name, target, err in (drift or ()):
        findings.append(AuditFinding(
            name, "config-drift", "-", "-",
            f"registered entry point target '{target}' no longer resolves "
            f"({err}); update tools/analysis/entrypoints.py if it moved"))
    for entry in registry:
        try:
            resolve_target(entry.target)
        except Exception as e:  # noqa: BLE001
            findings.append(AuditFinding(
                entry.name, "config-drift", "-", "-",
                f"registered entry point target '{entry.target}' no longer "
                f"resolves ({type(e).__name__}: {e}); update "
                f"tools/analysis/entrypoints.py if it moved"))
            continue
        findings += audit_entry(entry, modes)
    return findings


# --------------------------------------------------------------------------
# bench bridge + CI trace cache
# --------------------------------------------------------------------------
def paged_decode_dense_gather_free() -> int:
    """The PR-7 bench row, now answered by the auditor (single source of
    truth): 1 iff the paged decode entry points are dense-gather-free under
    the kernel tier AND the XLA oracle still materializes the dense shape."""
    registry, drift = build_registry()
    if drift:
        return 0
    decode = [e for e in registry if e.dense_shapes]
    if not decode:
        return 0
    findings: List[AuditFinding] = []
    for e in decode:
        findings += [f for f in audit_entry(e, modes=BOTH_MODES)
                     if f.rule == "no-dense-gather"]
    return 0 if findings else 1


def tree_digest(root: pathlib.Path) -> str:
    """Digest of every source file the traced jaxprs depend on — the CI
    cache key for skipping a re-trace on unchanged trees."""
    import jax
    h = hashlib.sha256()
    h.update(jax.__version__.encode())
    for pat in ("src/repro/**/*.py", "tools/analysis/*.py"):
        for p in sorted(pathlib.Path(root).glob(pat)):
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def cached_ok(cache_file: pathlib.Path, digest: str) -> bool:
    try:
        data = json.loads(pathlib.Path(cache_file).read_text())
    except (OSError, ValueError):
        return False
    return bool(data.get("clean")) and data.get("digest") == digest


def write_cache(cache_file: pathlib.Path, digest: str) -> None:
    out = pathlib.Path(cache_file)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"digest": digest, "clean": True}) + "\n")


def load_registry_module(path: pathlib.Path) -> Iterable[EntryPoint]:
    """Load a registry module (``REGISTRY`` list) from a file path — used by
    the known-bad fixture trees under tests/fixtures/analysis/."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("audit_fixture_registry",
                                                  str(path))
    assert spec is not None and spec.loader is not None, path
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return list(mod.REGISTRY)
