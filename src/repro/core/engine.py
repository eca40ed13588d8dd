"""HOBBIT OffloadEngine: serve a real JAX MoE model with host-resident
experts, a device-resident mixed-precision expert cache, the dynamic loader,
the adaptive predictor and the multidimensional cache manager — the full
system of Fig. 4, with *real numerics* (mixed-precision expert substitution
actually changes the computed logits; accuracy benchmarks measure that).

Scope: decoder-only MoE models whose body layers are all (attn + MoE FFN) —
the paper's model class (Mixtral / Phi-MoE shapes).

The engine performs the real cache/loader mechanics and numerics on
whatever backend JAX runs: on a TPU the pools live in HBM and the Pallas
kernels run compiled; on the CPU backend "device" and "host" share memory,
so only counts and numerics mean anything there.  It also records a routing
trace that core.simulator replays against hardware cost models — the
separation the paper uses for its Fig. 9 analysis.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import MultidimensionalCache
from repro.core.fleet_heat import FleetHeat
from repro.core.loader import (ON_DEMAND, DynamicExpertLoader, LoadTask,
                               StagingEngine, measure_link_bps)
from repro.core.policies import MULTIDIM, PolicyWeights
from repro.core.predictor import AdaptiveExpertPredictor
from repro.core.scoring import (PREC_HI, PREC_LO, PREC_SKIP, Thresholds,
                                precision_decisions)
from repro.core.simulator import TraceLayer
from repro.core.spans import Spans
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import unstack_layers
from repro.models.model import Batch, Model
from repro.quant.quantize import QTensor, dequantize, expert_nbytes, quantize

# the engine's host spans (core/spans.py, totals in stats()["spans"]): the
# decode step's root, its direct children (each hobbit.layer.* span opens
# once per MoE layer), an expert commit (inside stage_wait during decode)
# and one prefill chunk
SPANS = ("hobbit.decode", "hobbit.decode.prologue", "hobbit.layer.attn",
         "hobbit.layer.gate", "hobbit.layer.route", "hobbit.layer.prefetch",
         "hobbit.layer.stage_wait", "hobbit.layer.plan",
         "hobbit.layer.ship_overflow", "hobbit.layer.ffn",
         "hobbit.decode.logits", "hobbit.stage.commit",
         "hobbit.prefill.chunk")


def _without_experts(params):
    """The model params with every MoE layer's routed experts left out."""
    def strip(layer):
        ffn = layer.get("ffn")
        if not isinstance(ffn, dict) or "experts" not in ffn:
            return layer
        return {**layer, "ffn": {k: v for k, v in ffn.items()
                                 if k != "experts"}}
    return {**params, **{part: [strip(p) for p in params[part]]
                         for part in ("prefix", "blocks", "tail")}}


def _expert_refs(cfg, params):
    """Per layer, (its routed-expert leaves or None, the leading index of
    its block in a stacked leaf): nothing is sliced, so nothing is copied."""
    def refs(layer, lead):
        ffn = layer.get("ffn")
        experts = ffn.get("experts") if isinstance(ffn, dict) else None
        return experts, lead
    out = [refs(p, ()) for p in params["prefix"]]
    for i in range(cfg.num_blocks):
        out.extend(refs(p, (i,)) for p in params["blocks"])
    out.extend(refs(p, ()) for p in params["tail"])
    return out


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    hi_slots: int = 16
    lo_slots: int = 8
    thresholds: Thresholds = Thresholds(0.6, 0.9)
    policy: PolicyWeights = MULTIDIM
    prefetch_p: int = 2
    lo_bits: int = 4
    group_size: int = 64
    dynamic_loading: bool = True     # ablation switch (Fig. 16)
    prefetch: bool = True            # ablation switch (Fig. 17)
    compute_mode: str = "device"     # device | host (CPU-helper mode §4)
    # grouped decode: one batched gating matmul + one batched hi GEMM + one
    # batched lo dequant-GEMM per MoE layer instead of O(batch*top_k) tiny
    # per-expert dispatches.  False selects the original per-expert path
    # (the parity reference; also used automatically in host compute mode).
    grouped: bool = True
    # stage prefetch copies on a background executor so they overlap compute
    # in wall clock (double-buffered).  False drains them synchronously.
    async_prefetch: bool = True
    # multi-stream staging (core/loader.py StagingEngine): number of copy
    # streams sharing the modeled H2D link (default one hi- + one lo-
    # precision stream).  `ordered=True` with `streams=1` reproduces the
    # PR-2 single-worker FIFO scheduler bit-for-bit (the parity reference);
    # ordered=False issues biggest-gate-first within the nearest-deadline
    # layer and may downgrade queued hi copies to lo under link pressure.
    streams: int = 2
    ordered: bool = False
    # idle-link upgrade pass: re-issue hi copies for experts whose hi
    # prefetch was downgraded to lo under link pressure, hottest first,
    # whenever the hi stream is idle and no deadline work is queued — the
    # lo stand-in keeps serving (served_lo_expert_steps counts the
    # exposure) until the hi copy lands, then compute switches back to hi.
    # The substitution persists for as long as the link stays saturated
    # (hi reloads for substituted keys are suppressed so they can't stall
    # deadline barriers) and is undone at the first idle window.  False
    # restores the PR-4 per-token semantics bit-identically: a downgrade
    # serves lo for its own step only and the next step's hi request
    # blocking-loads hi on demand.
    upgrade: bool = True
    # modeled H2D link bandwidth in GB/s.  None measures the backend's
    # host->device copy rate at startup (budget accounting only); an
    # explicit value additionally *emulates* the link — each staged copy
    # occupies its stream for bytes/link seconds — so contended-link
    # behavior is reproducible on any backend (benchmarks/decode_speedup.py
    # uses this).
    link_gbps: Optional[float] = None
    # paged KV cache: slots draw kv_page_size-token pages from a shared pool
    # of kv_pages pages (None = the dense equivalent, batch*ceil(max_len/
    # page)) instead of each slot allocating max_len up front; prompts then
    # prefill in prefill_chunk-token chunks (see models/kv_pages.py).
    paged_kv: bool = False
    kv_page_size: int = 64
    kv_pages: Optional[int] = None
    prefill_chunk: int = 64
    # prefix sharing (paged KV only): admissions alias trie-matched prompt
    # prefix pages across slots with copy-on-write on divergence; admission
    # then only charges the unshared suffix (see models/kv_pages.py)
    prefix_sharing: bool = True


class OffloadEngine:
    def __init__(self, model: Model, params, ecfg: EngineConfig):
        cfg = model.cfg
        assert cfg.moe is not None, "OffloadEngine requires a MoE model"
        self.model = model
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.dtype = L._dt(cfg)
        # owner: main-thread
        self.spans = Spans(SPANS)
        self._steps = 0                 # decode steps (the root span's step)
        self._host_syncs = 0            # device->host reads in decode
        self._overflow_shipped_bytes = 0
        self._commit_shipped_bytes = 0
        # routed computations by the precision actually computed
        self._served = {PREC_HI: 0, PREC_LO: 0, PREC_SKIP: 0}

        # per-layer views for the decode loop, minus the experts: slicing a
        # stacked leaf copies it, and the engine serves experts from its
        # pools — a sliced copy would hold every expert on the device twice
        self.layer_params = unstack_layers(cfg, _without_experts(params))
        self.moe_layers = [i for i, m in enumerate(cfg.layer_is_moe()) if m]
        self.num_moe_layers = len(self.moe_layers)

        mc = cfg.moe
        d, f, e = cfg.d_model, mc.d_ff_expert, mc.num_experts
        wi_cols = 2 * f if cfg.ffn_activation == "swiglu" else f

        # ---- host storage: hi (dense) + lo (quantized) versions ----
        # built one expert at a time, sliced straight out of the (stacked)
        # leaf, so the device never holds more than one extra expert, in
        # its own dtype
        refs = _expert_refs(cfg, params)
        self.storage_hi: List[Dict[str, np.ndarray]] = []
        self.storage_lo: List[Dict[str, QTensor]] = []
        self.routers: List[np.ndarray] = []
        for li in self.moe_layers:
            experts, lead = refs[li]
            hi, lo = {}, {}
            for name in ("wi", "wo"):
                hi[name], lo[name] = self._host_copies(experts[name], lead,
                                                       e, ecfg, self.dtype)
            # host storage lives in numpy so background staging threads never
            # issue device computations
            self.storage_hi.append(hi)
            self.storage_lo.append(lo)
            self.routers.append(np.asarray(
                self.layer_params[li]["ffn"]["router"], np.float32))
        # routers pre-stacked on device for the grouped (B,D)@(D,E) gating
        self.routers_dev = jnp.asarray(np.stack(self.routers))   # (L, D, E)

        # ---- device pools ----
        # owner: main-thread — device pool handles are rebound on
        # commit; a background rebind would race the dispatch gather
        self.pool_hi = {
            "wi": jnp.zeros((ecfg.hi_slots, d, wi_cols), self.dtype),
            "wo": jnp.zeros((ecfg.hi_slots, f, d), self.dtype),
        }
        qi, qo = self.storage_lo[0]["wi"], self.storage_lo[0]["wo"]
        # owner: main-thread
        self.pool_lo = {
            "wi_data": jnp.zeros((ecfg.lo_slots, *qi.data.shape[1:]), jnp.int8),
            "wi_scale": jnp.zeros((ecfg.lo_slots, *qi.scale.shape[1:]), jnp.float32),
            "wo_data": jnp.zeros((ecfg.lo_slots, *qo.data.shape[1:]), jnp.int8),
            "wo_scale": jnp.zeros((ecfg.lo_slots, *qo.scale.shape[1:]), jnp.float32),
        }
        self._qmeta = dict(bits=ecfg.lo_bits, group_size=ecfg.group_size, orig_k=0)

        # ---- manager / loader / predictor ----
        # owner: main-thread
        # fleet heat is engine-lifetime (survives cache.new_sequence()): the
        # cross-request expert prior blended into the Eq. 3 cache priorities
        self.fleet = FleetHeat()
        self.cache = MultidimensionalCache(self.num_moe_layers, ecfg.hi_slots,
                                           ecfg.lo_slots, ecfg.policy,
                                           fleet=self.fleet)
        hi_b = expert_nbytes(d, f, 16)
        lo_b = expert_nbytes(d, f, ecfg.lo_bits, group_size=ecfg.group_size)
        self.expert_bytes = {PREC_HI: hi_b, PREC_LO: lo_b}
        self.loader = DynamicExpertLoader(
            self.cache, ecfg.thresholds if ecfg.dynamic_loading
            else Thresholds(1.0, 1.0),
            self._fetch, lambda prec: self.expert_bytes[prec])
        link_bps = (ecfg.link_gbps * 1e9 if ecfg.link_gbps
                    else measure_link_bps(hi_b))
        self.scheduler = StagingEngine(
            self.loader, self._stage, self._commit_staged,
            streams=ecfg.streams, ordered=ecfg.ordered, link_bps=link_bps,
            emulate_link=ecfg.link_gbps is not None, upgrade=ecfg.upgrade)
        self.predictor = AdaptiveExpertPredictor(
            self.routers, mc.top_k, p=ecfg.prefetch_p, fleet=self.fleet)

        # pending predictions: (Prediction, made_at_layer, batch_slot)
        self._pending_preds: List = []
        self.trace: List[List[TraceLayer]] = []
        self._jit_cache: Dict[str, callable] = {}
        self._expert_dispatches = 0     # grouped-path compute dispatches
        self._union_reloads = 0         # same-layer contention re-fetches
        self._layer_s_ema = 0.0         # per-layer compute EMA (deadline hints)
        self._layer_period_ema = 0.0    # full layer period EMA (stream feed)
        self._closed = False
        self.batch = 1
        self.max_len = 0
        self.active = np.ones((1,), bool)
        self.kv_pool = None             # PagedKVPool when ecfg.paged_kv
        self._admission = None          # ChunkedPrefill when ecfg.paged_kv
        self._pending_joins = {}        # dense-path incremental admissions
        self._unclaimed_joins = {}      # finished during a blocking join()

    @staticmethod
    def _host_copies(leaf, lead: Tuple[int, ...], num_experts: int,
                     ecfg: EngineConfig, dtype):
        """(hi, lo QTensor) host copies of one expert matrix stack: expert j
        is ``leaf[(*lead, j)]``, sliced, quantized and fetched alone.  hi is
        kept in ``dtype``, the hi pool's, so a commit ships exactly the bytes
        the pool holds and nothing is converted on the host after this."""
        hi, data, scale, q = None, [], [], None
        for j in range(num_experts):
            w = leaf[(*lead, j)]
            if hi is None:
                hi = np.empty((num_experts, *w.shape), dtype)
            hi[j] = np.asarray(w, dtype)
            q = quantize(w, bits=ecfg.lo_bits, group_size=ecfg.group_size)
            data.append(np.asarray(q.data))
            scale.append(np.asarray(q.scale))
        return hi, QTensor(data=np.stack(data), scale=np.stack(scale),
                           bits=q.bits, group_size=q.group_size,
                           orig_k=q.orig_k)

    # ------------------------------------------------------------------
    # device transfer
    # ------------------------------------------------------------------
    def _fetch(self, moe_idx: int, expert: int, precision: int, slot: int):
        """Write one expert's weights into a pool slot (the 'cudaMemcpy')."""
        if precision == PREC_HI:
            src = self.storage_hi[moe_idx]
            self.pool_hi["wi"] = self.pool_hi["wi"].at[slot].set(
                jnp.asarray(src["wi"][expert], self.dtype))
            self.pool_hi["wo"] = self.pool_hi["wo"].at[slot].set(
                jnp.asarray(src["wo"][expert], self.dtype))
        else:
            src = self.storage_lo[moe_idx]
            self.pool_lo["wi_data"] = self.pool_lo["wi_data"].at[slot].set(
                src["wi"].data[expert])
            self.pool_lo["wi_scale"] = self.pool_lo["wi_scale"].at[slot].set(
                src["wi"].scale[expert])
            self.pool_lo["wo_data"] = self.pool_lo["wo_data"].at[slot].set(
                src["wo"].data[expert])
            self.pool_lo["wo_scale"] = self.pool_lo["wo_scale"].at[slot].set(
                src["wo"].scale[expert])

    def _stage(self, moe_idx: int, expert: int, precision: int) -> dict:
        """Gather one expert's weight bytes from host storage into staging
        buffers (the host half of the transfer).  Read-only on shared state,
        so the async scheduler may run it on a background thread."""
        if precision == PREC_HI:
            src = self.storage_hi[moe_idx]
            return {"wi": np.ascontiguousarray(src["wi"][expert]),
                    "wo": np.ascontiguousarray(src["wo"][expert])}
        src = self.storage_lo[moe_idx]
        return {"wi_data": np.ascontiguousarray(src["wi"].data[expert]),
                "wi_scale": np.ascontiguousarray(src["wi"].scale[expert]),
                "wo_data": np.ascontiguousarray(src["wo"].data[expert]),
                "wo_scale": np.ascontiguousarray(src["wo"].scale[expert])}

    def _scatter_fn(self, n_tensors: int):
        """Jitted multi-tensor slot scatter (eager `.at[].set` pays ~ms of
        python dispatch per call on CPU; the jitted version is one fused
        update of every pool tensor)."""
        key = ("scatter", n_tensors)
        if key not in self._jit_cache:
            def hobbit_commit_scatter(pools, idx, values):
                return [p.at[idx].set(v.astype(p.dtype))
                        for p, v in zip(pools, values)]
            # donate the pool buffers: callers rebind the pools to the
            # returned arrays immediately, so keeping the inputs alive would
            # hold two full copies of every expert pool per commit
            self._jit_cache[key] = jax.jit(hobbit_commit_scatter,
                                           donate_argnums=0)
        return self._jit_cache[key]

    def _commit_staged(self, entries):
        """Write staged buffers into the device pools, one expert per
        scatter call: the scatter's shapes never depend on how many copies
        landed together, so steady decode compiles no new variant, and only
        one expert's bytes are in flight on the device at a time.
        entries: [(task_like_with_precision, slot, staged_dict)]."""
        with self.spans("hobbit.stage.commit"):
            for t, slot, buf in entries:
                pool = self.pool_hi if t.precision == PREC_HI else self.pool_lo
                self._commit_shipped_bytes += self._write_slot(pool, slot, buf)

    def _write_slot(self, arrays: Dict, slot: int, buf: dict) -> int:
        """Scatter one staged expert (``_stage``'s buffers, keyed as
        ``arrays``) into ``slot`` of the device arrays and rebind them; each
        buffer crosses as stored on the host (hi in the pool's dtype, so the
        scatter's cast is a no-op).  Returns the bytes that crossed."""
        names = tuple(arrays)
        host = [buf[n][None] for n in names]
        new = self._scatter_fn(len(names))(
            [arrays[n] for n in names], jnp.asarray([slot], jnp.int32),
            [jnp.asarray(a) for a in host])
        arrays.update(zip(names, new))
        return sum(a.nbytes for a in host)

    def _to_host(self, x, dtype=None) -> np.ndarray:
        """Read a device array to the host.  Every device->host read of a
        decode step goes through here and counts in ``host_syncs``."""
        self._host_syncs += 1
        return np.asarray(x, dtype)

    def _overflow_slots(self, pp: int) -> Dict[int, Dict[str, jax.Array]]:
        """One layer's device overflow slots for union-overflow experts
        (cache smaller than the layer's union demand), {precision: {name:
        array}}: ``pp`` zeros per precision made on the device, shaped and
        typed as the pools.  Made afresh for each layer and dropped after
        its ``grouped_ffn``: slots kept across layers would sit beside the
        commits' uploads, which set the step's peak memory.  Only entries
        written this layer are addressed.  The first slots of a size get an
        expert written to slot 0 of each, so that their scatter variants
        compile here and no later step compiles one."""
        name = f"overflow_slots_{pp}"
        first = name not in self._jit_cache
        pools = {PREC_HI: self.pool_hi, PREC_LO: self.pool_lo}
        slots = self._jit(name, lambda n=pp: {
            prec: {k: jnp.zeros((n, *a.shape[1:]), a.dtype)
                   for k, a in pool.items()}
            for prec, pool in pools.items()})()
        if first:
            for prec, arrays in slots.items():
                self._write_slot(arrays, 0, self._stage(0, 0, prec))
        return slots

    def _fetch_many(self, items: List[Tuple[int, int, int, int]]):
        """Blocking batched fetch into admitted pool slots: items =
        [(moe_idx, expert, precision, slot)], one scatter per expert.
        The decode hot paths go through `_stage`/`_commit_staged` directly
        (async prefetch, batched on-demand drain, overflow staging); this is
        the standalone batched-fetch entry point for warmup/pre-population
        and tests."""
        entries = []
        for mi, e, prec, slot in items:
            t = LoadTask(mi, e, int(prec), ON_DEMAND, self.expert_bytes[int(prec)])
            entries.append((t, slot, self._stage(mi, e, int(prec))))
            self.loader.loaded_bytes += t.bytes
            self.loader.n_loads[t.precision] += 1
        self._commit_staged(entries)

    # ------------------------------------------------------------------
    # jitted pieces
    # ------------------------------------------------------------------
    def _attn_step(self, p, x, cache, positions):
        cfg = self.cfg
        h = L.apply_norm(p["pre_norm"], x, cfg)
        out, new_cache = L.attn_decode(p["attn"], h, cache, positions, cfg, "attn")
        return x + out, new_cache

    def _attn_step_paged(self, p, x, kp, vp, table, positions, active):
        """Paged-KV attention step: same residual math as `_attn_step`, but
        K/V scatter/gather through the shared page pool."""
        cfg = self.cfg
        h = L.apply_norm(p["pre_norm"], x, cfg)
        out, kp, vp = L.paged_attn_decode(p["attn"], h, kp, vp, table,
                                          positions, active, cfg)
        return x + out, kp, vp

    def _attn_layer(self, li: int, x, *, table=None, active_dev=None):
        """Run layer li's attention against whichever KV layout is active,
        updating the layout's state in place.  Returns the residual stream."""
        p = self.layer_params[li]
        if self.ecfg.paged_kv:
            # page buffers donated: rebound to the outputs right below
            fn = self._jit("attn_paged", self._attn_step_paged,
                           donate=(2, 3))
            x, kp, vp = fn(p, x, self.kv_pool.k[li], self.kv_pool.v[li],
                           table, self.positions, active_dev)
            self.kv_pool.k[li], self.kv_pool.v[li] = kp, vp
            return x
        fn = self._jit("attn", self._attn_step)
        x, self.kv_cache[li] = fn(p, x, self.kv_cache[li], self.positions)
        return x

    def _paged_step_prologue(self, rows):
        """Grow every active slot's page chain for the token about to be
        written (copying shared pages off their sharers first — decode
        appending into an aliased prefix page must not corrupt it) and
        export the page table once per step."""
        pos = self._to_host(self.positions)
        for r in rows:
            p = int(pos[r])
            self.kv_pool.ensure(r, p + 1)
            self.kv_pool.make_writable(r, p, p + 1)
        return self.kv_pool.table_device(), jnp.asarray(self.active)

    def _ffn_input(self, p, x):
        return L.apply_norm(p["ffn_norm"], x, self.cfg)

    def _hi_expert(self, wi, wo, h):
        cfg = self.cfg
        z = h @ wi
        if cfg.ffn_activation == "swiglu":
            g, u = jnp.split(z, 2, axis=-1)
            z = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        else:
            z = jax.nn.gelu(z.astype(jnp.float32)).astype(h.dtype)
        return z @ wo

    def _lo_expert(self, wi_data, wi_scale, wo_data, wo_scale, h):
        cfg = self.cfg
        mc = cfg.moe
        d, f = cfg.d_model, mc.d_ff_expert
        qi = QTensor(wi_data, wi_scale, self.ecfg.lo_bits, self.ecfg.group_size, d)
        qo = QTensor(wo_data, wo_scale, self.ecfg.lo_bits, self.ecfg.group_size, f)
        z = (h.astype(jnp.float32) @ dequantize(qi)).astype(h.dtype)
        if cfg.ffn_activation == "swiglu":
            g, u = jnp.split(z, 2, axis=-1)
            z = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        else:
            z = jax.nn.gelu(z.astype(jnp.float32)).astype(h.dtype)
        return (z.astype(jnp.float32) @ dequantize(qo)).astype(h.dtype)

    def _activate(self, z):
        cfg = self.cfg
        if cfg.ffn_activation == "swiglu":
            g, u = jnp.split(z, 2, axis=-1)
            return jax.nn.silu(g.astype(jnp.float32)).astype(z.dtype) * u
        return jax.nn.gelu(z.astype(jnp.float32)).astype(z.dtype)

    def _grouped_ffn(self, hi_wi, hi_wo, lo_wi_data, lo_wi_scale, lo_wo_data,
                     lo_wo_scale, ovf_hi_wi, ovf_hi_wo, ovf_lo_wi_data,
                     ovf_lo_wi_scale, ovf_lo_wo_data, ovf_lo_wo_scale, h,
                     hi_rows, hi_ranks, hi_slot, lo_rows, lo_ranks, lo_slot,
                     w_hi, w_lo):
        """All active (row, expert) pairs of one MoE layer in two batched
        dispatches: one hi GEMM over the gathered hi-pool slots and one lo
        dequant-GEMM over the gathered lo-pool slots.  Index arrays have
        fixed length P = batch * top_k (padded entries carry row == batch,
        which the gather clips and the scatter drops), so each batch size
        compiles exactly once.  Hi-pair outputs land in a (B, K, D) grid at
        unique (row, rank) cells — combine order is fixed by the rank axis,
        keeping per-slot numerics independent of neighbouring slots; the lo
        half fuses GEMM + gated combine in `kops.grouped_dequant_combine`
        (pair rows are emitted non-decreasing by the builder below, the
        kernel's scatter contract).

        The ovf_* buffers carry union-overflow experts (cache smaller than
        the layer's union demand at batch > 1): they are appended after the
        pool slots, so slot index >= pool size addresses the overflow buffer
        and pairs never evict a slot a neighbouring pair already claimed."""
        ecfg = self.ecfg
        b, _, d = h.shape
        k = w_hi.shape[1]
        hs = h[:, 0]                                        # (B, D)
        # ---- one batched hi GEMM ----
        all_hi_wi = jnp.concatenate([hi_wi, ovf_hi_wi], axis=0)
        all_hi_wo = jnp.concatenate([hi_wo, ovf_hi_wo], axis=0)
        xh = hs[jnp.clip(hi_rows, 0, b - 1)]                # (P, D)
        z = jnp.einsum("pd,pdc->pc", xh, all_hi_wi[hi_slot])
        out_hi = jnp.einsum("pf,pfd->pd", self._activate(z), all_hi_wo[hi_slot])
        # ---- one batched lo dequant-GEMM ----
        all_lo = [jnp.concatenate([a, o], axis=0) for a, o in (
            (lo_wi_data, ovf_lo_wi_data), (lo_wi_scale, ovf_lo_wi_scale),
            (lo_wo_data, ovf_lo_wo_data), (lo_wo_scale, ovf_lo_wo_scale))]
        xl = hs[jnp.clip(lo_rows, 0, b - 1)]
        zl = kops.grouped_dequant_matmul(
            xl, all_lo[0][lo_slot], all_lo[1][lo_slot],
            bits=ecfg.lo_bits, group_size=ecfg.group_size).astype(hs.dtype)
        # second lo GEMM fused with the gated per-row combine: pad pairs
        # (row == b) carry weight 0 and are dropped in-kernel
        lo_w_pair = jnp.where(
            lo_rows < b, w_lo[jnp.clip(lo_rows, 0, b - 1), lo_ranks], 0.0)
        y_lo = kops.grouped_dequant_combine(
            self._activate(zl), all_lo[2][lo_slot], all_lo[3][lo_slot],
            lo_rows, lo_w_pair, bits=ecfg.lo_bits,
            group_size=ecfg.group_size, num_rows=b)         # (B, D) f32
        # ---- hi combine (unique (row, rank) cells; OOB pads dropped) ----
        grid = jnp.zeros((b, k, d), jnp.float32)
        grid = grid.at[hi_rows, hi_ranks].set(out_hi.astype(jnp.float32),
                                              mode="drop")
        y = (grid * w_hi[..., None]).sum(axis=1) + y_lo
        wsum = (w_hi + w_lo).sum(axis=1)[:, None]           # disjoint weights
        y = jnp.where(wsum > 0, y / jnp.where(wsum > 0, wsum, 1.0), 0.0)
        return y[:, None, :]                                # (B, 1, D)

    def _jit(self, name, fn, donate=()):
        """``fn`` jitted once per engine as the program ``hobbit_<name>``
        (``jit_hobbit_<name>`` in a profiler trace)."""
        if name not in self._jit_cache:
            def program(*args):
                return fn(*args)
            program.__name__ = program.__qualname__ = f"hobbit_{name}"
            self._jit_cache[name] = jax.jit(program, donate_argnums=donate)
        return self._jit_cache[name]

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def start_batch(self, batch: int, max_len: int):
        """Allocate per-slot KV caches and reset serving state for a new
        (possibly multi-request) batch.  All slots start active; continuous-
        batching schedulers toggle individual slots via join()/release()."""
        self._check_open()
        self.batch = batch
        self.max_len = max_len
        self.scheduler.flush()          # land any cross-batch in-flight loads
        self.cache.new_sequence()
        if self.ecfg.paged_kv:
            from repro.models.kv_pages import ChunkedPrefill
            self.kv_cache = None
            self.kv_pool = self.model.init_cache(
                batch, max_len, paged=True,
                page_size=self.ecfg.kv_page_size,
                num_pages=self.ecfg.kv_pages,
                prefix_sharing=self.ecfg.prefix_sharing)
            self._admission = ChunkedPrefill(self.model, self.params,
                                             self.kv_pool,
                                             chunk=self.ecfg.prefill_chunk)
        else:
            self.kv_cache = [
                {"k": jnp.zeros((batch, max_len, self.cfg.num_kv_heads,
                                 self.cfg.resolved_head_dim), self.dtype),
                 "v": jnp.zeros((batch, max_len, self.cfg.num_kv_heads,
                                 self.cfg.resolved_head_dim), self.dtype)}
                for _ in range(self.cfg.num_layers)]
        self.positions = jnp.zeros((batch,), jnp.int32)
        self.active = np.ones((batch,), bool)
        self.trace = []
        self._pending_preds = []        # (Prediction, made_at_layer, slot)
        self._pending_joins = {}        # abandoned admissions don't leak
        self._unclaimed_joins = {}

    def start_sequence(self, max_len: int, batch: int = 1):
        self.start_batch(batch, max_len)

    # ---------------- prefill / slot admission ----------------
    def _prefill_fn(self):
        key = ("prefill", self.max_len)
        if key not in self._jit_cache:
            max_len = self.max_len
            self._jit_cache[key] = jax.jit(
                lambda p, b: self.model.prefill(p, b, max_len))
        return self._jit_cache[key]

    def _flat_decode_cache(self, cache):
        """Flatten model.prefill's nested cache into the engine's per-layer
        list.  Valid for the engine's model class: every layer is a full-
        window "attn" + MoE block, so every entry is a max_len k/v pair."""
        cfg = self.cfg
        assert all(k == "attn" for k in cfg.layer_kinds()), cfg.layer_kinds()
        flat = [dict(c) for c in cache["prefix"]]
        for bi in range(cfg.num_blocks):
            for j in range(cfg.period):
                flat.append(jax.tree_util.tree_map(lambda a, bi=bi: a[bi],
                                                   cache["blocks"][j]))
        flat.extend(dict(c) for c in cache["tail"])
        return flat

    def prefill_batch(self, prompts) -> np.ndarray:
        """Real prefill: run the whole prompt batch through the dense model
        in one jitted call (prefill is compute-bound and touches every expert
        anyway — the offload cache only serves the decode phase, matching the
        paper's deployment), then adopt the KV cache in the engine's
        per-layer layout.  Returns last-token logits (B, V)."""
        self._check_open()
        prompts = np.asarray(prompts, np.int32)
        b, s = prompts.shape
        assert b == self.batch, (b, self.batch)
        if self.ecfg.paged_kv:
            # chunked prefill through the page pool, still dense compute
            for r in range(b):
                self._admission.begin(r, prompts[r],
                                      reserve_tokens=self.max_len)
            done = {}
            while len(done) < b:
                done.update(self._admission.step())
            self.positions = jnp.full((b,), s, jnp.int32)
            self.active[:] = True
            return np.stack([done[r] for r in range(b)])
        batch = Batch(tokens=jnp.asarray(prompts),
                      loss_mask=jnp.ones((b, s), jnp.float32))
        logits, cache, positions = self._prefill_fn()(self.params, batch)
        self.kv_cache = self._flat_decode_cache(cache)
        self.positions = positions
        self.active[:] = True
        return np.asarray(logits, np.float32)

    def join(self, slot: int, prompt) -> np.ndarray:
        """Admit one request into a free slot mid-flight (blocking).  This is
        a documented thin wrapper over ``join_begin``/``join_step`` — the ONE
        blocking-join implementation lives in ``serving.api._blocking_join``
        and is shared by every backend.  Returns logits (V,)."""
        self._check_open()
        assert 0 <= slot < self.batch, (slot, self.batch)
        from repro.serving.api import _blocking_join
        return _blocking_join(self, slot, prompt)

    def _join_dense(self, slot: int, prompt) -> np.ndarray:
        """Dense-KV one-shot admission body: batch=1 prefill, KV scattered
        into the slot's cache rows.  Called from join_step."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        batch = Batch(tokens=jnp.asarray(prompt[None]),
                      loss_mask=jnp.ones((1, len(prompt)), jnp.float32))
        logits, cache, positions = self._prefill_fn()(self.params, batch)
        one = self._flat_decode_cache(cache)
        for li in range(self.cfg.num_layers):
            self.kv_cache[li] = jax.tree_util.tree_map(
                lambda dst, src: dst.at[slot].set(src[0].astype(dst.dtype)),
                self.kv_cache[li], one[li])
        self.positions = self.positions.at[slot].set(int(positions[0]))
        self.active[slot] = True
        self._pending_preds = [pp for pp in self._pending_preds
                               if pp[2] != slot]
        return np.asarray(logits[0], np.float32)

    def join_begin(self, slot: int, prompt, reserve_tokens=None):
        """Start an incremental admission into `slot`.  Paged KV: reserves
        pages for `reserve_tokens` (default max_len) and queues the prompt
        for chunked prefill.  Dense KV: stashes the prompt (join_step then
        runs the one-shot prefill)."""
        self._check_open()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.ecfg.paged_kv:
            self._admission.begin(slot, prompt,
                                  reserve_tokens=reserve_tokens or self.max_len)
        else:
            self._pending_joins[slot] = prompt

    def join_step(self) -> Dict[int, np.ndarray]:
        """Advance every in-progress admission one prefill chunk (ONE shared
        jitted call under paged KV); completed slots become active.  Returns
        {slot: last-token logits} — including logits another slot's blocking
        ``join`` finished but did not claim."""
        with self.spans("hobbit.prefill.chunk"):
            done: Dict[int, np.ndarray] = dict(self._unclaimed_joins)
            self._unclaimed_joins = {}
            if self.ecfg.paged_kv:
                done.update(self._admission.step())
                for slot in done:
                    plen = int(self.kv_pool.lens[slot])
                    self.positions = self.positions.at[slot].set(plen)
                    self.active[slot] = True
                    self._pending_preds = [pp for pp in self._pending_preds
                                           if pp[2] != slot]
                return done
            for slot, prompt in list(self._pending_joins.items()):
                del self._pending_joins[slot]
                done[slot] = self._join_dense(slot, prompt)
            return done

    def can_admit(self, tokens: int, *, prompt=None) -> bool:
        """KV-capacity admission gate: paged KV checks unreserved pages
        (with `prompt`, net of the best prefix-sharing plan — aliased
        prefix pages cost nothing); dense KV always admits (slots are
        pre-allocated to max_len)."""
        if self.ecfg.paged_kv and self.kv_pool is not None:
            return self.kv_pool.can_reserve(tokens, prompt=prompt)
        return True

    def release(self, slot: int):
        """Free a slot (its KV rows become junk until the next join; paged
        KV returns the slot's pages to the pool).  Retires the request from
        the fleet heat map (one decay tick — the cross-request prior ages
        by requests, not wall clock)."""
        self.active[slot] = False
        self._pending_preds = [pp for pp in self._pending_preds
                               if pp[2] != slot]
        if self.ecfg.paged_kv and self.kv_pool is not None:
            self.kv_pool.release(slot)
        self.fleet.retire_request()

    def pause(self, slot: int) -> Dict:
        """Preempt `slot` mid-decode: snapshot its KV state to host, then
        free the slot (paged KV returns its pages to the pool — the
        snapshot is taken FIRST, so aliased prefix pages are copied out
        while the remaining sharers keep the originals and their
        refcounts).  Returns the opaque snapshot for ``resume``.  The
        expert cache is untouched: it is shared, and the fleet heat map
        keeps the victim's experts warm for its return."""
        self._check_open()
        pos = int(np.asarray(self.positions)[slot])
        self._pending_preds = [pp for pp in self._pending_preds
                               if pp[2] != slot]
        if self.ecfg.paged_kv and self.kv_pool is not None:
            snap = self.kv_pool.snapshot_slot(slot)
            self.kv_pool.release(slot)
            self.active[slot] = False
            return {"layout": "paged", "position": pos, "kv": snap}
        rows = [{"k": np.asarray(c["k"][slot]), "v": np.asarray(c["v"][slot])}
                for c in self.kv_cache]
        self.active[slot] = False
        return {"layout": "dense", "position": pos, "cache": rows}

    def resume(self, slot: int, snapshot: Dict) -> None:
        """Reinstate a paused request into (a possibly different) `slot`
        from its ``pause`` snapshot; decode continues logits-identically.
        Paged KV raises PagePoolExhausted when the pool cannot host the
        snapshot right now (the scheduler keeps it and retries)."""
        self._check_open()
        if snapshot["layout"] == "paged":
            self.kv_pool.restore_slot(slot, snapshot["kv"])
        else:
            for li, row in enumerate(snapshot["cache"]):
                c = self.kv_cache[li]
                self.kv_cache[li] = {
                    "k": c["k"].at[slot].set(jnp.asarray(row["k"])),
                    "v": c["v"].at[slot].set(jnp.asarray(row["v"]))}
        self.positions = self.positions.at[slot].set(int(snapshot["position"]))
        self.active[slot] = True

    # ---------------- batched HOBBIT decode ----------------
    def decode_step_batch(self, tokens) -> np.ndarray:
        """One batched HOBBIT decode step.  tokens: (B,) int32; returns
        logits (B, V).  Inactive slots ride through attention (their rows
        are junk and cheap) but take no part in gating, expert loading,
        expert compute, the trace, or position advancement.  Expert loading
        is the union of all active slots' demands; precision decisions stay
        per-slot, so each slot's numerics match its own batch=1 run.

        Two implementations share this contract: the grouped path (default —
        one batched gating matmul, one hi GEMM and one lo dequant-GEMM per
        MoE layer, async double-buffered prefetch) and the per-expert
        reference path (``grouped=False`` or host compute mode), kept as the
        numerics baseline the parity tests compare against."""
        self._check_open()
        self._steps += 1
        with self.spans.step("hobbit.decode", self._steps):
            if self.ecfg.grouped and self.ecfg.compute_mode == "device":
                return self._decode_step_batch_grouped(tokens)
            return self._decode_step_batch_reference(tokens)

    # ---- shared per-layer bookkeeping ----
    def _score_pending_preds(self, mi: int, tops: Dict[int, np.ndarray]):
        """Score the accuracy of earlier predictions that targeted layer mi."""
        still_pending = []
        for pred, made_at, r in self._pending_preds:
            if pred.layer == mi:
                if r in tops:
                    self.predictor.record_accuracy(pred, tops[r].tolist(),
                                                   mi - made_at)
            elif pred.layer > mi:
                still_pending.append((pred, made_at, r))
        self._pending_preds = still_pending

    def _push_pending(self, pr, mi: int, r: int):
        """Record a pending prediction, keeping AT MOST ONE per (layer,
        slot): a newer prediction (made closer to the target layer, from
        fresher hidden state) replaces an older one, so record_accuracy
        scores each (layer, slot) exactly once."""
        self._pending_preds = [
            (p, m, rr) for p, m, rr in self._pending_preds
            if not (p.layer == pr.layer and rr == r)]
        self._pending_preds.append((pr, mi, r))

    def _prefetch_predictions(self, mi: int, rows, h_host, *,
                              use_async: bool) -> Dict[int, object]:
        """Adaptive prefetch for subsequent layers (§3.3).

        Pending-prediction bookkeeping is deduplicated: previously both the
        adaptive walk and the extra plain next-layer prediction appended an
        entry for the same (layer, slot), so record_accuracy could count a
        layer twice per slot and pred_entry[r] was silently overwritten.
        Now the walk's entry wins and the plain next-layer prediction (kept
        for the trace/simulator) is only recorded when the walk did not
        already cover layer mi+1."""
        pred_entry: Dict[int, object] = {}
        # merge all rows' predictions per target layer so the async scheduler
        # stages ONE job per layer instead of one tiny job per batch slot;
        # each (expert, precision) pair keeps the LARGEST gate any row gave
        # it — the staging engine issues biggest-gate-first under contention
        merged: Dict[int, List[Tuple[int, int]]] = {}
        gmax: Dict[Tuple[int, int, int], float] = {}
        for r in rows:
            walk = self.predictor.adaptive_walk(h_host[r], mi, self.cache,
                                                self.loader.th)
            walk_layers = set()
            for pr, dec in walk:
                pairs = merged.setdefault(pr.layer, [])
                for e, d, g in zip(pr.experts, dec, pr.gate_vals):
                    if (int(e), int(d)) not in pairs:
                        pairs.append((int(e), int(d)))
                    gk = (pr.layer, int(e), int(d))
                    gmax[gk] = max(gmax.get(gk, 0.0), float(g))
                self._push_pending(pr, mi, r)
                walk_layers.add(pr.layer)
                if pr.layer == mi + 1:
                    pred_entry[r] = pr
            if mi + 1 not in walk_layers:
                nxt = self.predictor.predict_layers(h_host[r], mi, 1)
                if nxt:
                    self._push_pending(nxt[0], mi, r)
                    pred_entry[r] = nxt[0]
        for layer, pairs in merged.items():
            experts = [e for e, _ in pairs]
            dec = np.asarray([d for _, d in pairs])
            gates = np.asarray([gmax[(layer, e, d)] for e, d in pairs])
            if use_async:
                self.scheduler.submit_prefetch(layer, experts, dec,
                                               current_layer=mi, gates=gates)
            else:
                self.loader.enqueue_prefetch(layer, experts, dec)
        return pred_entry

    def _trace_entry(self, mi, r, tops, gates, pred_entry) -> TraceLayer:
        pe = pred_entry.get(r)
        return TraceLayer(
            experts=tops[r].tolist(), gate_vals=gates[r],
            pred_experts=pe.experts if (pe and pe.layer == mi + 1) else None,
            pred_gate_vals=pe.gate_vals if (pe and pe.layer == mi + 1) else None)

    # ---- grouped implementation (the serving hot path) ----
    def _decode_step_batch_grouped(self, tokens) -> np.ndarray:
        cfg, ecfg, mc = self.cfg, self.ecfg, self.cfg.moe
        sp = self.spans
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        assert tokens.shape[0] == self.batch, (tokens.shape, self.batch)
        b, k = self.batch, mc.top_k
        rows = [r for r in range(b) if self.active[r]]
        self.cache.advance_token()
        tok = jnp.asarray(tokens[:, None])
        x = jnp.take(self.params["embed"], tok, axis=0)
        if cfg.scale_embedding:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)

        ffn_in = self._jit("ffn_in", self._ffn_input)
        # fused router: stacked matmul + softmax + top-k in one dispatch
        # (kernels/ops.gating_topk; ref path on CPU, pallas on TPU)
        gate_fn = self._jit(
            "gate", lambda h2, w: kops.gating_topk(h2, w[None], top_k=k))
        grouped_ffn = self._jit("grouped_ffn", self._grouped_ffn)
        combine_fn = self._jit("residual_add",
                               lambda xx, yy: xx + yy.astype(xx.dtype))

        table = active_dev = None
        if ecfg.paged_kv:
            with sp("hobbit.decode.prologue"):
                table, active_dev = self._paged_step_prologue(rows)
        row_trace = {r: [] for r in rows}
        for mi, li in enumerate(self.moe_layers):
            with sp("hobbit.layer.attn", layer=mi):
                t_layer0 = time.perf_counter()
                # deadline hint: the staging engine budgets queued copies
                # against (target_layer - mi) * per-layer compute seconds of
                # link time
                self.scheduler.set_deadline_clock(mi, self._layer_s_ema,
                                                  self._layer_period_ema)
                p = self.layer_params[li]
                x = self._attn_layer(li, x, table=table, active_dev=active_dev)
                h = ffn_in(p, x)                               # (B,1,D)
                # forcing h here keeps the pending attn/ffn-in compute out
                # of the gate span
                h_host = self._to_host(h[:, 0], np.float32)    # (B,D)

            # ---- gating: ONE (B,D)@(D,E) matmul from the stacked routers --
            with sp("hobbit.layer.gate", layer=mi):
                _, vals_g, idx_g = gate_fn(h[:, 0], self.routers_dev[mi])
                vals_np = self._to_host(vals_g[0], np.float32)  # (B,K)
                idx_np = self._to_host(idx_g[0], np.int32)      # (B,K)

            with sp("hobbit.layer.route", layer=mi):
                tops: Dict[int, np.ndarray] = {}
                gates: Dict[int, np.ndarray] = {}
                for r in rows:
                    tops[r] = idx_np[r]
                    gates[r] = vals_np[r]
                    for e, g in zip(tops[r], gates[r]):
                        self.fleet.observe((mi, int(e)), float(g))

                self._score_pending_preds(mi, tops)

                # ---- on-demand scoring (union over slots) ----
                # Hard-pin this layer's experts BEFORE prefetch admission:
                # async submit_prefetch admits (and may evict) at submit
                # time, so without the pins it could evict a resident
                # expert this very layer is about to compute with.
                self.loader.new_layer()
                for r in rows:
                    self.loader.score_and_enqueue(mi, tops[r].tolist(),
                                                  gates[r], clear_pins=False)

            with sp("hobbit.layer.prefetch", layer=mi):
                pred_entry = {}
                if ecfg.prefetch:
                    pred_entry = self._prefetch_predictions(
                        mi, rows, h_host, use_async=ecfg.async_prefetch)

            # ---- loading ----
            with sp("hobbit.layer.stage_wait", layer=mi):
                t_load0 = time.perf_counter()
                if ecfg.async_prefetch:
                    # barrier: land every prefetch targeting this layer
                    # (copies have been staging in the background since they
                    # were predicted), then blocking-load the residual miss
                    # set in one batched transfer
                    self.scheduler.wait(mi)
                    self.scheduler.drain_on_demand(self.loader.take_queued(),
                                                   mi)
                else:
                    self.loader.drain(mi)
                t_load = time.perf_counter() - t_load0
                # a commit's upload holds device memory until its scatter
                # has run: land them all before grouped_ffn is enqueued, or
                # they sit beside its temporaries and raise peak memory
                jax.block_until_ready((self.pool_hi, self.pool_lo))

            # ---- grouped expert compute: 1 hi + 1 lo dispatch ----
            with sp("hobbit.layer.plan", layer=mi):
                plan, ovf = self._plan_layer(mi, rows, tops, gates)

            with sp("hobbit.layer.ship_overflow", layer=mi):
                # only the experts this layer reloaded cross, each into its
                # overflow slot
                slots = self._overflow_slots(b * k)
                for prec, i, buf in ovf:
                    self._overflow_shipped_bytes += self._write_slot(
                        slots[prec], i, buf)
                if ovf:
                    # landed before grouped_ffn, as the commits are
                    jax.block_until_ready(slots)

            with sp("hobbit.layer.ffn", layer=mi):
                y = grouped_ffn(self.pool_hi["wi"], self.pool_hi["wo"],
                                self.pool_lo["wi_data"],
                                self.pool_lo["wi_scale"],
                                self.pool_lo["wo_data"],
                                self.pool_lo["wo_scale"],
                                *slots[PREC_HI].values(),
                                *slots[PREC_LO].values(), h,
                                *(jnp.asarray(a) for a in plan))
                # freed once grouped_ffn has run, before the next layer's
                # commits
                del slots
                self._expert_dispatches += 1
                x = combine_fn(x, y)

                for r in rows:
                    row_trace[r].append(self._trace_entry(mi, r, tops, gates,
                                                          pred_entry))
                # downgrade markers are per-token decisions: consumed this
                # layer, never carried into later steps' precision choices
                self.scheduler.retire_layer(mi)
                # per-layer compute EMA (loading time excluded) — the
                # staging engine's deadline clock budgets link bytes against
                # it — and full-period EMA (loading included) — its per-pump
                # stream feed
                dt_full = time.perf_counter() - t_layer0
                dt = dt_full - t_load
                self._layer_s_ema = (dt if self._layer_s_ema == 0.0
                                     else 0.8 * self._layer_s_ema + 0.2 * dt)
                self._layer_period_ema = (
                    dt_full if self._layer_period_ema == 0.0
                    else 0.8 * self._layer_period_ema + 0.2 * dt_full)

        with sp("hobbit.decode.logits"):
            self.positions = self.positions + jnp.asarray(
                self.active.astype(np.int32))
            for r in rows:
                self.trace.append(row_trace[r])
            lg = self.model.logits(self.params, x)[:, 0]
            return self._to_host(lg, np.float32)

    def _plan_layer(self, mi: int, rows, tops, gates):
        """Place every routed (row, rank) of MoE layer mi in the hi GEMM, the
        lo GEMM or neither, by the precision actually served, and count it
        in ``served_*``.  Returns ``_grouped_ffn``'s index and weight arrays
        (hi_rows, hi_ranks, hi_slots, lo_rows, lo_ranks, lo_slots, w_hi,
        w_lo; fixed length P = batch * top_k) and the union-overflow
        experts to ship, [(precision, overflow slot, staged buffers)].

        Union-overflow pairs (a same-layer neighbour's admission evicted this
        expert: union demand > pool) ride in the device overflow slots
        (``_overflow_slots``), addressed after the pool slots, instead of
        re-admitting — re-admission could evict a slot an earlier pair
        already claimed, corrupting its compute.  The re-fetch still counts
        as a miss + load so hit_ratio reflects real traffic under
        contention."""
        ecfg = self.ecfg
        b, k = self.batch, self.cfg.moe.top_k
        pp = b * k
        hi_rows = np.full(pp, b, np.int32)
        hi_ranks = np.zeros(pp, np.int32)
        hi_slots = np.zeros(pp, np.int32)
        lo_rows = np.full(pp, b, np.int32)
        lo_ranks = np.zeros(pp, np.int32)
        lo_slots = np.zeros(pp, np.int32)
        w_hi = np.zeros((b, k), np.float32)
        w_lo = np.zeros((b, k), np.float32)
        ovf = []
        n_hi = n_lo = 0
        n_ovf_hi = n_ovf_lo = 0
        for r in rows:
            dec = precision_decisions(gates[r], self.loader.th)
            for j in range(k):
                d_ = int(dec[j])
                if d_ == PREC_SKIP:
                    self._served[PREC_SKIP] += 1
                    continue
                e = int(tops[r][j])
                is_hi = d_ == PREC_HI
                slot = self.cache.lookup((mi, e), is_hi)
                if (slot is not None and is_hi
                        and self.cache.is_inflight((mi, e), True)):
                    # an upgrade re-copy owns the slot but its bytes are
                    # still landing (wait() never blocks on upgrades); the
                    # slot holds no hi weights yet
                    slot = None
                if (slot is None and is_hi and ecfg.async_prefetch
                        and self.scheduler.serves_lo_downgrade(mi, e)):
                    # issue-time precision downgrade: the staging engine
                    # replaced this hi copy with a lo one under link
                    # pressure — compute from the lo pool until an idle-link
                    # upgrade lands the hi copy
                    is_hi = False
                    slot = self.cache.lookup((mi, e), False)
                    self.scheduler.served_lo_expert_steps += 1
                if slot is None:
                    # staged in the precision served: a downgraded hi pair
                    # reloads its lo copy
                    prec = PREC_HI if is_hi else PREC_LO
                    if is_hi:
                        self.cache.stats.misses_hi += 1
                        ovf.append((prec, n_ovf_hi, self._stage(mi, e, prec)))
                        slot = ecfg.hi_slots + n_ovf_hi
                        n_ovf_hi += 1
                    else:
                        self.cache.stats.misses_lo += 1
                        ovf.append((prec, n_ovf_lo, self._stage(mi, e, prec)))
                        slot = ecfg.lo_slots + n_ovf_lo
                        n_ovf_lo += 1
                    self.loader.loaded_bytes += self.expert_bytes[prec]
                    self.loader.n_loads[prec] += 1
                    self._union_reloads += 1
                if is_hi:
                    hi_rows[n_hi], hi_ranks[n_hi] = r, j
                    hi_slots[n_hi] = slot
                    w_hi[r, j] = gates[r][j]
                    n_hi += 1
                else:
                    lo_rows[n_lo], lo_ranks[n_lo] = r, j
                    lo_slots[n_lo] = slot
                    w_lo[r, j] = gates[r][j]
                    n_lo += 1
                self._served[PREC_HI if is_hi else PREC_LO] += 1
        return (hi_rows, hi_ranks, hi_slots, lo_rows, lo_ranks, lo_slots,
                w_hi, w_lo), ovf

    # ---- per-expert reference implementation (parity baseline) ----
    def _decode_step_batch_reference(self, tokens) -> np.ndarray:
        cfg, ecfg, mc = self.cfg, self.ecfg, self.cfg.moe
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        assert tokens.shape[0] == self.batch, (tokens.shape, self.batch)
        rows = [r for r in range(self.batch) if self.active[r]]
        self.cache.advance_token()
        tok = jnp.asarray(tokens[:, None])
        x = jnp.take(self.params["embed"], tok, axis=0)
        if cfg.scale_embedding:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)

        ffn_in = self._jit("ffn_in", self._ffn_input)
        hi_exp = self._jit("hi", self._hi_expert)
        lo_exp = self._jit("lo", self._lo_expert)

        table = active_dev = None
        if ecfg.paged_kv:
            table, active_dev = self._paged_step_prologue(rows)
        row_trace = {r: [] for r in rows}
        for mi, li in enumerate(self.moe_layers):
            p = self.layer_params[li]
            x = self._attn_layer(li, x, table=table, active_dev=active_dev)
            h = ffn_in(p, x)                                   # (B,1,D)
            h_host = self._to_host(h[:, 0], np.float32)        # (B,D)

            # ---- gate (the paper's Expert Scorer input), per slot ----
            tops: Dict[int, np.ndarray] = {}
            gates: Dict[int, np.ndarray] = {}
            for r in rows:
                logits = h_host[r] @ self.routers[mi]
                probs = np.exp(logits - logits.max())
                probs /= probs.sum()
                tops[r] = np.argsort(-probs)[: mc.top_k]
                gates[r] = probs[tops[r]]
                for e, g in zip(tops[r], gates[r]):
                    self.fleet.observe((mi, int(e)), float(g))

            self._score_pending_preds(mi, tops)
            pred_entry = {}
            if ecfg.prefetch:
                pred_entry = self._prefetch_predictions(mi, rows, h_host,
                                                        use_async=False)

            # ---- on-demand scoring + loading (union over slots) ----
            self.loader.new_layer()
            for r in rows:
                self.loader.score_and_enqueue(mi, tops[r].tolist(), gates[r],
                                              clear_pins=False)
            self.loader.drain(mi)

            # ---- expert compute from cache slots, per slot ----
            y_rows = []
            for r in range(self.batch):
                if r not in row_trace:
                    y_rows.append(jnp.zeros_like(h[r : r + 1]))
                    continue
                hr = h[r : r + 1]
                dec = precision_decisions(gates[r], self.loader.th)
                y = jnp.zeros_like(hr)
                wsum = 0.0
                for e, d_, w in zip(tops[r], dec, gates[r]):
                    self._served[int(d_)] += 1
                    if d_ == PREC_SKIP:
                        continue
                    is_hi = d_ == PREC_HI
                    slot = self.cache.lookup((mi, e), is_hi)
                    if slot is None:
                        # union-overflow reload (see grouped path)
                        if is_hi:
                            self.cache.stats.misses_hi += 1
                        else:
                            self.cache.stats.misses_lo += 1
                        slot, _ = self.cache.admit((mi, int(e)), is_hi, mi)
                        self._fetch(mi, int(e), int(d_), slot)
                        self.loader.loaded_bytes += self.expert_bytes[int(d_)]
                        self.loader.n_loads[int(d_)] += 1
                        self._union_reloads += 1
                    if self.ecfg.compute_mode == "host":
                        out = self._host_expert(mi, int(e), d_,
                                                self._to_host(hr, np.float32))
                        out = jnp.asarray(out, hr.dtype)
                    elif is_hi:
                        out = hi_exp(self.pool_hi["wi"][slot],
                                     self.pool_hi["wo"][slot], hr)
                    else:
                        out = lo_exp(self.pool_lo["wi_data"][slot],
                                     self.pool_lo["wi_scale"][slot],
                                     self.pool_lo["wo_data"][slot],
                                     self.pool_lo["wo_scale"][slot], hr)
                    y = y + float(w) * out.astype(jnp.float32)
                    wsum += float(w)
                if wsum > 0:
                    y = y / wsum                                # renormalize (skips)
                y_rows.append(y)
                row_trace[r].append(self._trace_entry(mi, r, tops, gates,
                                                      pred_entry))
            x = x + jnp.concatenate(y_rows, axis=0).astype(x.dtype)

        self.positions = self.positions + jnp.asarray(
            self.active.astype(np.int32))
        for r in rows:
            self.trace.append(row_trace[r])
        lg = self.model.logits(self.params, x)[:, 0]
        return self._to_host(lg, np.float32)

    def close(self):
        """Release the staging engine's worker threads (also released
        automatically when the engine is garbage-collected).  Idempotent:
        a second close is a no-op; stepping a closed engine raises
        RuntimeError cleanly instead of failing deep inside the executor."""
        if self._closed:
            return
        self._closed = True
        self.scheduler.flush()
        self.scheduler.shutdown()

    def _check_open(self):
        """Raise cleanly when serving entry points run after close()."""
        if self._closed:
            raise RuntimeError("OffloadEngine is closed; create a new engine "
                               "(close() released its staging threads)")

    def decode_token(self, token: int) -> np.ndarray:
        """One HOBBIT decode step (batch=1 legacy API).  Returns logits (V,)."""
        assert self.batch == 1, "decode_token is batch=1; use decode_step_batch"
        return self.decode_step_batch(np.asarray([int(token)], np.int32))[0]

    def _host_expert(self, mi, e, d_, h):
        """CPU-GPU cooperative mode (§4): run the expert on host weights."""
        cfg = self.cfg
        if d_ == PREC_HI:
            # numpy math in bfloat16 is slow and rounds per op: upcast
            wi = np.asarray(self.storage_hi[mi]["wi"][e], np.float32)
            wo = np.asarray(self.storage_hi[mi]["wo"][e], np.float32)
        else:
            wi = self._to_host(dequantize(jax.tree_util.tree_map(
                lambda a: a[e], self.storage_lo[mi]["wi"])))
            wo = self._to_host(dequantize(jax.tree_util.tree_map(
                lambda a: a[e], self.storage_lo[mi]["wo"])))
        z = h @ wi
        if cfg.ffn_activation == "swiglu":
            g, u = np.split(z, 2, axis=-1)
            z = (g / (1 + np.exp(-g))) * u
        else:
            z = 0.5 * z * (1 + np.tanh(np.sqrt(2 / np.pi) * (z + 0.044715 * z**3)))
        return z @ wo

    # ------------------------------------------------------------------
    # evaluation helpers
    # ------------------------------------------------------------------
    def generate(self, prompt: List[int], new_tokens: int,
                 max_len: Optional[int] = None) -> List[int]:
        max_len = max_len or (len(prompt) + new_tokens + 1)
        self.start_sequence(max_len)
        lg = None
        for t in prompt:
            lg = self.decode_token(int(t))
        out = []
        for _ in range(new_tokens):
            nxt = int(np.argmax(lg))
            out.append(nxt)
            lg = self.decode_token(nxt)
        return out

    def score_nll(self, tokens: List[int], max_len: Optional[int] = None) -> float:
        """Teacher-forced mean NLL through the offload path (accuracy evals)."""
        max_len = max_len or (len(tokens) + 1)
        self.start_sequence(max_len)
        nll, n = 0.0, 0
        lg = self.decode_token(int(tokens[0]))
        for t in tokens[1:]:
            p = lg - lg.max()
            p = p - np.log(np.exp(p).sum())
            nll -= p[int(t)]
            n += 1
            lg = self.decode_token(int(t))
        return nll / max(n, 1)

    def stats(self) -> Dict:
        """Fully JSON-serializable engine counters: cache hit/miss/eviction
        breakdown (with hit_ratio), loader traffic, predictor accuracy, and
        the async scheduler's wall-clock stall/overlap accounting."""
        s = {
            "cache": self.cache.stats.to_dict(),
            "loads_hi": self.loader.n_loads[PREC_HI],
            "loads_lo": self.loader.n_loads[PREC_LO],
            "skips": self.loader.n_skips,
            "loaded_bytes": self.loader.loaded_bytes,
            "pred_accuracy": {int(d): float(a)
                              for d, a in self.predictor.accuracy().items()},
            # the gate span's total (grouped decode)
            "gating_s": self.spans.seconds("hobbit.layer.gate"),
            "expert_dispatches": self._expert_dispatches,
            "union_reloads": self._union_reloads,
            # which kernel implementation each hot-path op dispatched/traced
            # ("<op>.<xla|pallas|pallas_interpret>" -> count): a TPU run
            # showing only .xla counts is silently benchmarking the einsum
            # oracle path
            "kernel_dispatch": kops.dispatch_counts(),
            # KV page-pool pressure (zeros under the dense KV layout)
            "kv_pages_used": 0, "kv_pages_total": 0, "kv_page_fraction": 0.0,
            # {span name: {"s": seconds, "n": count}} (core/spans.py)
            "spans": self.spans.totals(),
            "overflow_shipped_bytes": self._overflow_shipped_bytes,
            "commit_shipped_bytes": self._commit_shipped_bytes,
            "host_syncs": self._host_syncs,
            "served_hi": self._served[PREC_HI],
            "served_lo": self._served[PREC_LO],
            "served_skip": self._served[PREC_SKIP],
        }
        if self.ecfg.paged_kv and self.kv_pool is not None:
            s.update(self.kv_pool.stats())
        s.update(self.scheduler.stats())
        return s
