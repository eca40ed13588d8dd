"""Unified serving API: one `InferenceBackend` protocol in front of both the
dense (resident-weights) decode path and the HOBBIT mixed-precision expert
offloading engine, so schedulers, launchers, examples and benchmarks drive a
single interface regardless of where the experts live.

The protocol is slot-oriented to support *continuous batching*
(`serving.batching.BatchingServer`): a backend holds `batch` KV-cache slots,
a finished request `release()`s its slot mid-flight, and a queued request
`join()`s the freed slot at the next step without disturbing its neighbours.

    backend methods
    ---------------
    start_batch(batch, max_len)      allocate B slots (all marked active)
    prefill(prompts (B,S)) -> (B,V)  full-batch prefill, last-token logits
    join(slot, prompt (S,)) -> (V,)  admit one request into a slot mid-flight
    join_begin(slot, prompt, ...)    start an *incremental* admission
    join_step() -> {slot: (V,)}      advance all admissions by one chunk
    can_admit(tokens, *, prompt)     does KV capacity exist for a request?
                                     (with `prompt`: net of prefix sharing)
    pause(slot) -> snapshot          preempt a slot mid-decode: snapshot its
                                     KV to host and release the slot
    resume(slot, snapshot)           re-admit a paused request from snapshot
    release(slot)                    free a slot (and its KV pages)
    step(tokens (B,)) -> (B,V)       one decode step for the whole batch
    stats() -> dict                  backend-specific counters

Backends are constructed through ``make_backend(BackendConfig(...))`` —
one typed config instead of the historical kwarg sprawl (the old
``make_backend(kind, ..., paged=..., page_size=...)`` form still works for
one release behind a DeprecationWarning).

KV memory comes in two layouts for `DenseBackend`, selected at
construction; `HobbitBackend` always uses the paged one:

  * dense (``DenseBackend`` default): ``start_batch`` allocates a
    (B, max_len) cache up front — simple, but one long request inflates
    every slot.
  * paged (``DenseBackend(paged=True)``, and the offload engine): a fixed
    device-resident page pool (`repro.models.kv_pages.PagedKVPool`,
    ~64-token pages) with per-slot page tables; a slot's memory grows with
    its actual length, ``release`` returns its pages to the pool, and
    admission reserves a request's full budget so decode never starves.
    Prompts are prefilled in fixed-size chunks (``join_begin``/``join_step``)
    that the scheduler interleaves with decode steps.

Usage::

    from repro.serving.api import DenseBackend, HobbitBackend, generate
    from repro.core import EngineConfig, OffloadEngine

    backend = DenseBackend(model, params)                  # resident weights
    res = generate(backend, prompts, new_tokens=32)        # same helper...

    eng = OffloadEngine(model, params, EngineConfig(hi_slots=16, lo_slots=8))
    res = generate(HobbitBackend(eng), prompts, 32)        # ...either way

`generate` / `score_nll` here are thin helpers over the protocol; the
continuous-batching scheduler lives in `serving.batching`.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.kv_pages import ChunkedPrefill, PagedKVPool
from repro.models.model import Batch, Model, supports_paged_kv
from repro.serving.decode import (GenerateResult, make_prefill_step,
                                  sample_token)


@runtime_checkable
class InferenceBackend(Protocol):
    """Slot-oriented decode interface served by the continuous scheduler."""

    model: Model

    def start_batch(self, batch: int, max_len: int) -> None:
        """Allocate `batch` KV slots able to reach `max_len` tokens each
        (dense: up-front per-slot buffers; paged: a shared page pool)."""
        ...

    def prefill(self, prompts: np.ndarray) -> np.ndarray:
        """Full-batch prefill of (B, S) prompts; returns last-token logits
        (B, V) and marks every slot active."""
        ...

    def join(self, slot: int, prompt: np.ndarray) -> np.ndarray:
        """Admit one request into a free slot mid-flight (blocking: runs the
        whole prompt).  Returns last-token logits (V,)."""
        ...

    def join_begin(self, slot: int, prompt: np.ndarray,
                   reserve_tokens: Optional[int] = None) -> None:
        """Start an incremental admission into `slot`, reserving
        `reserve_tokens` of KV capacity (prompt + decode budget) so the
        request can never hit pool exhaustion mid-decode."""
        ...

    def join_step(self) -> dict:
        """Advance every in-progress admission by one prefill chunk (one
        shared jitted call where the backend supports it).  Returns
        {slot: last-token logits (V,)} for admissions that completed."""
        ...

    def can_admit(self, tokens: int, *, prompt=None) -> bool:
        """True iff KV capacity for a request of `tokens` total length is
        available right now (dense backends: always).  `prompt` (the token
        ids about to be admitted, keyword-only) lets paged backends price
        the request net of prefix sharing: a prompt whose prefix aliases
        already-resident pages only needs pages for its unshared suffix."""
        ...

    def pause(self, slot: int) -> dict:
        """Preempt an *active* slot mid-decode: snapshot its KV state to
        host (paged backends gather the slot's written pages through its
        table; pages aliased by other slots keep those sharers' refcounts),
        release the slot, and return an opaque snapshot for `resume`.  The
        scheduler uses this to evict a low-priority victim so a more urgent
        request can take its slot/pages."""
        ...

    def resume(self, slot: int, snapshot: dict) -> None:
        """Re-admit a paused request — possibly into a different slot —
        from its `pause` snapshot: restore KV content and position, then
        mark the slot active.  Decode after resume is logits-identical to
        the unpreempted run."""
        ...

    def release(self, slot: int) -> None:
        """Free a slot: its rows become junk until the next join, and any
        KV pages it held return to the pool."""
        ...

    def step(self, tokens: np.ndarray) -> np.ndarray:
        """One decode step for the whole batch ((B,) tokens -> (B, V)
        logits); inactive slots ride along but are not advanced."""
        ...

    def stats(self) -> dict:
        """JSON-serializable backend counters (uniform keys: load_stall_s,
        overlap_fraction, precision_downgrades, issue_reorders,
        link_utilization, kv_pages_used, kv_page_fraction, ...)."""
        ...

    def close(self) -> None:
        """Release backend resources (offload backends: staging worker
        threads).  Idempotent; serving entry points raise RuntimeError after
        close instead of failing deep inside an executor."""
        ...


# --------------------------------------------------------------------------
# shared protocol plumbing
# --------------------------------------------------------------------------

def _blocking_join(backend, slot: int, prompt) -> np.ndarray:
    """THE blocking-join implementation.

    Both backends' `join` (and the engine's) are documented thin wrappers
    over this loop: begin an incremental admission, then drive `join_step`
    until `slot` completes.  Other pending admissions advance alongside;
    their finished logits are stashed on the backend (`_unclaimed_joins`)
    and stay claimable by the next `join_step` call."""
    backend.join_begin(slot, np.asarray(prompt, np.int32).reshape(-1))
    while True:
        done = backend.join_step()
        lg = done.pop(slot, None)
        backend._unclaimed_joins.update(done)
        if lg is not None:
            return lg


# --------------------------------------------------------------------------
# dense (resident-weights) backend
# --------------------------------------------------------------------------

def _scatter_slot(dst_cache, src_cache, slot: int):
    """Write a batch=1 prefill cache into row `slot` of a batched cache.

    The nested decode-cache layout puts the batch axis at 0 for prefix/tail
    entries, 1 for scanned-block entries (stacked (num_blocks, B, ...)), and
    2 for the whisper enc_kv buffer."""

    def ax0(b, o):
        return b.at[slot].set(o[0].astype(b.dtype))

    def ax1(b, o):
        return b.at[:, slot].set(o[:, 0].astype(b.dtype))

    tmap = jax.tree_util.tree_map
    out = {
        "prefix": [tmap(ax0, b, o) for b, o in
                   zip(dst_cache["prefix"], src_cache["prefix"])],
        "blocks": [tmap(ax1, b, o) for b, o in
                   zip(dst_cache["blocks"], src_cache["blocks"])],
        "tail": [tmap(ax0, b, o) for b, o in
                 zip(dst_cache["tail"], src_cache["tail"])],
    }
    if "enc_kv" in dst_cache:
        out["enc_kv"] = dst_cache["enc_kv"].at[:, :, slot].set(
            src_cache["enc_kv"][:, :, 0].astype(dst_cache["enc_kv"].dtype))
    return out


def _gather_slot(src_cache, slot: int):
    """Read row `slot` of a batched decode cache out as a batch=1 cache —
    the exact inverse of `_scatter_slot` (same per-entry batch axes), so a
    `pause` snapshot re-scatters bit-identically on `resume`."""

    def ax0(b):
        return b[slot:slot + 1]

    def ax1(b):
        return b[:, slot:slot + 1]

    tmap = jax.tree_util.tree_map
    out = {
        "prefix": [tmap(ax0, b) for b in src_cache["prefix"]],
        "blocks": [tmap(ax1, b) for b in src_cache["blocks"]],
        "tail": [tmap(ax0, b) for b in src_cache["tail"]],
    }
    if "enc_kv" in src_cache:
        out["enc_kv"] = src_cache["enc_kv"][:, :, slot:slot + 1]
    return out


class DenseBackend:
    """All weights resident on device; jitted prefill + decode step.

    ``paged=True`` swaps the per-slot (B, max_len) cache for a shared
    `PagedKVPool` (page_size-token pages, pool of `kv_pages` pages —
    default the dense equivalent) and prefills prompts in
    `prefill_chunk`-token chunks; requires `supports_paged_kv(model.cfg)`."""

    def __init__(self, model: Model, params, *, jit: bool = True,
                 paged: bool = False, page_size: int = 64,
                 kv_pages: Optional[int] = None, prefill_chunk: int = 64,
                 prefix_sharing: bool = True):
        self.model = model
        self.params = params
        self._jit = jit
        self.paged = paged
        self.page_size = page_size
        self.kv_pages = kv_pages
        self.prefill_chunk = prefill_chunk
        self.prefix_sharing = prefix_sharing
        if paged and not supports_paged_kv(model.cfg):
            raise ValueError(f"arch {model.cfg.name} does not support "
                             "the paged KV layout")

        def step(params, cache, tokens, positions, active):
            # active mask: released slots must not consume MoE dispatch
            # capacity (their junk rows would crowd live tokens at batch > 8)
            return model.decode_step(params, cache, tokens, positions,
                                     active=active)

        self._step = jax.jit(step, donate_argnums=1) if jit else step
        # donate the page buffers (args 1, 2 after params): the pool is
        # rebound to the outputs immediately, mirroring the dense cache
        self._paged_step = (jax.jit(model.decode_step_paged,
                                    donate_argnums=Model.PAGED_DECODE_DONATE)
                            if jit else model.decode_step_paged)
        self._prefill_fns = {}          # max_len -> (jitted) prefill
        self.kv: Optional[PagedKVPool] = None
        self._admission: Optional[ChunkedPrefill] = None
        self._pending_joins: dict = {}  # non-paged incremental admissions
        self._unclaimed_joins: dict = {}  # finished during a blocking join
        self.batch = 0
        self.max_len = 0

    def _prefill(self, max_len: int):
        if max_len not in self._prefill_fns:
            fn = make_prefill_step(self.model, max_len)
            self._prefill_fns[max_len] = jax.jit(fn) if self._jit else fn
        return self._prefill_fns[max_len]

    def start_batch(self, batch: int, max_len: int) -> None:
        """Allocate serving state: dense (B, max_len) cache, or — paged —
        (re)start the page pool (buffers are rebuilt only when shape-relevant
        parameters changed)."""
        self.batch, self.max_len = batch, max_len
        self.positions = jnp.zeros((batch,), jnp.int32)
        self.active = np.ones((batch,), bool)
        self._pending_joins = {}
        self._unclaimed_joins = {}
        if not self.paged:
            self.cache = self.model.init_cache(batch, max_len)
            return
        self.kv = self.model.init_cache(batch, max_len, paged=True,
                                        page_size=self.page_size,
                                        num_pages=self.kv_pages,
                                        prefix_sharing=self.prefix_sharing)
        self._admission = ChunkedPrefill(self.model, self.params, self.kv,
                                         chunk=self.prefill_chunk,
                                         jit=self._jit)

    def prefill(self, prompts) -> np.ndarray:
        """Full-batch prefill.  Paged: chunked prefill with every row
        reserving the full max_len (dense budget semantics).  Returns
        last-token logits (B, V)."""
        prompts_np = np.asarray(prompts, np.int32)
        if self.paged:
            # chunked prefill over the whole batch: every row reserves the
            # full max_len (same budget semantics as the dense allocator)
            for r in range(prompts_np.shape[0]):
                self._admission.begin(r, prompts_np[r],
                                      reserve_tokens=self.max_len)
            done: dict = {}
            while len(done) < prompts_np.shape[0]:
                done.update(self._admission.step())
            out = np.stack([done[r] for r in range(prompts_np.shape[0])])
            self.positions = jnp.asarray(
                [prompts_np.shape[1]] * prompts_np.shape[0], jnp.int32)
            self.active[:] = True
            return out
        prompts = jnp.asarray(prompts_np)
        batch = Batch(tokens=prompts, loss_mask=jnp.ones(prompts.shape))
        logits, self.cache, self.positions = self._prefill(self.max_len)(
            self.params, batch)
        self.active[:] = True
        return np.asarray(logits, np.float32)

    def join(self, slot: int, prompt) -> np.ndarray:
        """Blocking admission — a documented thin wrapper over
        `join_begin`/`join_step` (`_blocking_join`, the single blocking-join
        implementation shared by every backend).  Paged slots reserve the
        full max_len; concurrently pending admissions advance alongside and
        their finished logits stay claimable by the next join_step."""
        return _blocking_join(self, slot, prompt)

    def join_begin(self, slot: int, prompt,
                   reserve_tokens: Optional[int] = None) -> None:
        """Start an incremental admission.  Paged: reserves KV pages for
        `reserve_tokens` (default max_len) and queues the prompt for chunked
        prefill.  Dense: stashes the prompt; join_step runs it one-shot."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.paged:
            self._admission.begin(slot, prompt,
                                  reserve_tokens=reserve_tokens or self.max_len)
        else:
            self._pending_joins[slot] = prompt

    def join_step(self) -> dict:
        """Advance admissions one chunk (paged: ONE shared jitted call over
        every pending prompt; dense: each pending prompt's one-shot prefill).
        Completed slots are activated; returns their logits, plus any slots
        that finished inside an earlier blocking `join` and were not yet
        claimed."""
        done: dict = dict(self._unclaimed_joins)
        self._unclaimed_joins = {}
        if self.paged:
            done.update(self._admission.step())
            for slot in done:
                plen = int(self.kv.lens[slot])
                self.positions = self.positions.at[slot].set(plen)
                self.active[slot] = True
            return done
        for slot, prompt in list(self._pending_joins.items()):
            del self._pending_joins[slot]
            done[slot] = self._join_oneshot(slot, prompt)
        return done

    def _join_oneshot(self, slot: int, prompt) -> np.ndarray:
        """Unpaged admission: one batch=1 prefill, its cache scattered into
        the slot's rows."""
        prompt = jnp.asarray(np.asarray(prompt, np.int32).reshape(1, -1))
        batch = Batch(tokens=prompt, loss_mask=jnp.ones(prompt.shape))
        logits, one_cache, positions = self._prefill(self.max_len)(
            self.params, batch)
        self.cache = _scatter_slot(self.cache, one_cache, slot)
        self.positions = self.positions.at[slot].set(int(positions[0]))
        self.active[slot] = True
        return np.asarray(logits[0], np.float32)

    def can_admit(self, tokens: int, *, prompt=None) -> bool:
        """Paged: does the pool have unreserved pages for `tokens`?  With
        `prompt`, the pool prices the best prefix-sharing plan — aliased
        prefix pages are free, only the unshared suffix needs reservable
        pages.  Dense: always (the (B, max_len) slot is pre-allocated)."""
        if self.paged:
            return self.kv.can_reserve(tokens, prompt=prompt)
        return True

    def pause(self, slot: int) -> dict:
        """Preempt `slot` mid-decode: snapshot its KV to host and free the
        slot.  Paged: the snapshot gathers the slot's written pages through
        its table *before* release, so pages aliased by other slots keep
        those sharers' refcounts (only this slot's references drop)."""
        pos = int(np.asarray(self.positions)[slot])
        if self.paged:
            snap = self.kv.snapshot_slot(slot)
            self.kv.release(slot)
            self.active[slot] = False
            return {"layout": "paged", "position": pos, "kv": snap}
        cache = jax.tree_util.tree_map(np.asarray,
                                       _gather_slot(self.cache, slot))
        self.active[slot] = False
        return {"layout": "dense", "position": pos, "cache": cache}

    def resume(self, slot: int, snapshot: dict) -> None:
        """Re-admit a paused request into `slot` (any free slot works): the
        snapshot's KV bytes are written back verbatim, so decode continues
        logits-identical to the unpreempted run."""
        if self.paged:
            self.kv.restore_slot(slot, snapshot["kv"])
        else:
            self.cache = _scatter_slot(self.cache, snapshot["cache"], slot)
        self.positions = self.positions.at[slot].set(snapshot["position"])
        self.active[slot] = True

    def release(self, slot: int) -> None:
        """Free a slot; paged slots return their pages to the pool for the
        next queued request."""
        self.active[slot] = False
        if self.paged and self.kv is not None:
            self.kv.release(slot)

    def step(self, tokens) -> np.ndarray:
        """One decode step for the whole batch; under paged KV the step
        first grows each active slot's page chain for the token about to be
        written, then scatters/gathers through the page table."""
        tokens = jnp.asarray(np.asarray(tokens, np.int32).reshape(-1, 1))
        if self.paged:
            pos_host = np.asarray(self.positions)
            for r in range(self.batch):
                if self.active[r]:
                    p = int(pos_host[r])
                    self.kv.ensure(r, p + 1)
                    # decode appending into a shared (aliased) page copies
                    # it off first — readers keep the original
                    self.kv.make_writable(r, p, p + 1)
            logits, ks, vs = self._paged_step(
                self.params, self.kv.k, self.kv.v, self.kv.table_device(),
                tokens, self.positions, jnp.asarray(self.active))
            self.kv.k, self.kv.v = list(ks), list(vs)
        else:
            logits, self.cache = self._step(self.params, self.cache, tokens,
                                            self.positions,
                                            jnp.asarray(self.active))
        # only active slots advance; freed slots idle at their last position
        self.positions = self.positions + jnp.asarray(
            self.active.astype(np.int32))
        return np.asarray(logits, np.float32)

    def stats(self) -> dict:
        """Uniform backend counters; resident weights never stall on expert
        transfers, so load_stall_s/overlap_fraction are 0.  kv_* keys report
        page-pool pressure (zeros under the dense allocator)."""
        s = {"backend": "dense", "batch": self.batch, "max_len": self.max_len,
             "load_stall_s": 0.0, "overlap_fraction": 0.0,
             "precision_downgrades": 0, "issue_reorders": 0,
             "link_utilization": 0.0, "per_stream_bytes": [],
             "kv_pages_used": 0, "kv_pages_total": 0,
             "kv_page_fraction": 0.0, "prefix_hit_tokens": 0,
             "cow_copies": 0, "aliased_page_fraction": 0.0}
        if self.paged and self.kv is not None:
            s.update(self.kv.stats())
        return s

    def close(self) -> None:
        """Uniform teardown hook: resident weights hold no staging threads,
        so this is a no-op (idempotent by construction)."""


# --------------------------------------------------------------------------
# HOBBIT offload backend
# --------------------------------------------------------------------------

class HobbitBackend:
    """`OffloadEngine` behind the protocol: batched mixed-precision decode
    with union-of-slots expert loading and a real (dense, compute-bound)
    chunked prefill.  Its KV is always paged, through the same
    `PagedKVPool` / `ChunkedPrefill` machinery as `DenseBackend(paged=True)`
    (page settings in `EngineConfig`)."""

    def __init__(self, engine):
        self.engine = engine
        self.model = engine.model

    def start_batch(self, batch: int, max_len: int) -> None:
        """Allocate engine serving state (the KV page pool) for `batch`
        slots."""
        self.engine.start_batch(batch, max_len)

    def prefill(self, prompts) -> np.ndarray:
        """Full-batch chunked prefill with dense compute (prefill touches
        every expert anyway; the offload cache only serves decode)."""
        return self.engine.prefill_batch(prompts)

    def join(self, slot: int, prompt) -> np.ndarray:
        """Blocking mid-flight admission of one request into `slot` — the
        engine's `join` is itself a thin wrapper over the shared
        `_blocking_join` loop (one implementation, not three)."""
        return self.engine.join(slot, prompt)

    def join_begin(self, slot: int, prompt,
                   reserve_tokens: Optional[int] = None) -> None:
        """Start an incremental (chunked) admission."""
        self.engine.join_begin(slot, prompt, reserve_tokens=reserve_tokens)

    def join_step(self) -> dict:
        """Advance every in-progress admission by one prefill chunk."""
        return self.engine.join_step()

    def can_admit(self, tokens: int, *, prompt=None) -> bool:
        """KV-capacity gate for admission (with `prompt`, the request is
        priced net of prefix sharing)."""
        return self.engine.can_admit(tokens, prompt=prompt)

    def pause(self, slot: int) -> dict:
        """Preempt `slot` mid-decode: snapshot its KV pages (prefix-sharing
        refcounts handled by the pool) to host and free the slot for a more
        urgent request."""
        return self.engine.pause(slot)

    def resume(self, slot: int, snapshot: dict) -> None:
        """Restore a paused request's KV and position into `slot`; decode
        continues logits-identical to the unpreempted run."""
        self.engine.resume(slot, snapshot)

    def release(self, slot: int) -> None:
        """Free a slot and its KV pages."""
        self.engine.release(slot)

    def step(self, tokens) -> np.ndarray:
        """One batched HOBBIT decode step ((B,) tokens -> (B, V) logits)."""
        return self.engine.decode_step_batch(tokens)

    def stats(self) -> dict:
        """Engine counters (cache/loader/predictor/scheduler/KV-pool) tagged
        with the backend name."""
        s = dict(self.engine.stats())
        s["backend"] = "hobbit"
        return s

    def close(self) -> None:
        """Release the engine's staging worker threads (idempotent; the
        scheduler calls this on teardown)."""
        self.engine.close()


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    """Typed backend construction config — the ONE argument `make_backend`
    consumes (mirrored 1:1 by `launch/serve.py` flags).

    kind            "dense" (resident weights) or "hobbit" (offload engine)
    jit             jit the dense prefill/decode steps
    paged           paged KV layout (shared page pool) for kind="dense"; the
                    offload engine always pages, and `paged=True` hands it
                    the four page fields below
    page_size       tokens per KV page
    kv_pages        pool size in pages (None: the dense equivalent)
    prefill_chunk   tokens per chunked-prefill call
    prefix_sharing  radix prefix cache over the paged pool
    engine          `core.EngineConfig` for kind="hobbit" (None: defaults);
                    `paged=True` overrides its page fields
    """
    kind: str = "dense"
    jit: bool = True
    paged: bool = False
    page_size: int = 64
    kv_pages: Optional[int] = None
    prefill_chunk: int = 64
    prefix_sharing: bool = True
    engine: Optional[object] = None


_UNSET = object()   # distinguishes "not passed" from any explicit value


def make_backend(kind, model: Model, params, *, engine_config=_UNSET,
                 jit=_UNSET, paged=_UNSET, page_size=_UNSET, kv_pages=_UNSET,
                 prefill_chunk=_UNSET, prefix_sharing=_UNSET):
    """Factory for launchers: ``make_backend(BackendConfig(...), model,
    params)``.  A bare string kind (``make_backend("dense", model, params)``)
    is accepted as shorthand for the all-defaults config; passing any of the
    historical keyword arguments is DEPRECATED (they are folded into a
    BackendConfig behind a DeprecationWarning and removed next release)."""
    legacy = {name: val for name, val in [
        ("engine", engine_config), ("jit", jit), ("paged", paged),
        ("page_size", page_size), ("kv_pages", kv_pages),
        ("prefill_chunk", prefill_chunk), ("prefix_sharing", prefix_sharing),
    ] if val is not _UNSET}
    if isinstance(kind, BackendConfig):
        if legacy:
            raise TypeError(
                "make_backend(BackendConfig(...)) takes no keyword "
                f"arguments; fold {sorted(legacy)} into the config")
        cfg = kind
    else:
        if legacy:
            warnings.warn(
                "make_backend(kind, ..., **kwargs) is deprecated; pass "
                "make_backend(BackendConfig(kind=..., ...), model, params)",
                DeprecationWarning, stacklevel=2)
        cfg = BackendConfig(kind=kind, **legacy)

    if cfg.kind == "dense":
        return DenseBackend(model, params, jit=cfg.jit, paged=cfg.paged,
                            page_size=cfg.page_size, kv_pages=cfg.kv_pages,
                            prefill_chunk=cfg.prefill_chunk,
                            prefix_sharing=cfg.prefix_sharing)
    if cfg.kind == "hobbit":
        from repro.core.engine import EngineConfig, OffloadEngine
        ecfg = cfg.engine or EngineConfig()
        if cfg.paged:
            ecfg = dataclasses.replace(ecfg, kv_page_size=cfg.page_size,
                                       kv_pages=cfg.kv_pages,
                                       prefill_chunk=cfg.prefill_chunk,
                                       prefix_sharing=cfg.prefix_sharing)
        eng = OffloadEngine(model, params, ecfg)
        return HobbitBackend(eng)
    raise ValueError(f"unknown backend kind: {cfg.kind!r}")


# --------------------------------------------------------------------------
# protocol-level helpers (generate / score_nll for any backend)
# --------------------------------------------------------------------------

def generate(backend: InferenceBackend, prompts, new_tokens: int, *,
             max_len: Optional[int] = None, temperature: float = 0.0,
             seed: int = 0) -> GenerateResult:
    """Greedy/temperature generation through any backend.  prompts: (B, S)."""
    prompts = np.asarray(prompts, np.int32)
    b, s = prompts.shape
    max_len = max_len or (s + new_tokens + 1)
    backend.start_batch(b, max_len)

    t0 = time.time()
    lg = backend.prefill(prompts)
    t1 = time.time()

    key = jax.random.PRNGKey(seed)
    out = [prompts]
    tok = np.asarray(sample_token(jnp.asarray(lg), key, temperature))
    for i in range(new_tokens):
        out.append(np.asarray(tok)[:, None])
        if i == new_tokens - 1:
            break
        key, sub = jax.random.split(key)
        lg = backend.step(tok)
        tok = np.asarray(sample_token(jnp.asarray(lg), sub, temperature))
    t2 = time.time()
    return GenerateResult(np.concatenate(out, axis=1), t1 - t0, t2 - t1,
                          new_tokens)


def score_nll(backend: InferenceBackend, tokens, *,
              max_len: Optional[int] = None) -> float:
    """Teacher-forced mean NLL through any backend's decode path (the first
    token enters via a 1-token join/prefill; every later token is a decode
    step, so offload backends are exercised on their serving path)."""
    tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
    max_len = max_len or (len(tokens) + 1)
    backend.start_batch(1, max_len)
    lg = backend.join(0, np.asarray(tokens[:1], np.int32))
    nll, n = 0.0, 0
    for t in tokens[1:]:
        p = np.asarray(lg, np.float64)
        p -= p.max()
        p -= np.log(np.exp(p).sum())
        nll -= p[t]
        n += 1
        lg = backend.step(np.asarray([t], np.int32))[0]
    return float(nll / max(n, 1))
