"""Paged KV cache + chunked prefill admission tests: logits parity of both
backends' paged layout against the dense model's unpaged run, chunked-
prefill equivalence to one-shot
prefill, page reclamation on mid-flight release with immediate re-admission,
pool-exhaustion behavior (queued request waits, never crashes), and the new
stats fields (kv_pages_used / kv_page_fraction / admission_wait_s)."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import EngineConfig, OffloadEngine, Thresholds
from repro.models import build_model
from repro.models.kv_pages import PagedKVPool, PagePoolExhausted
from repro.serving.api import DenseBackend, HobbitBackend, generate
from repro.serving.batching import BatchingServer, Request


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=2, d_model=64,
                        vocab=128)
    # ample capacity: MoE token drops would otherwise differ between chunked
    # and one-shot prefill (capacity is computed per call)
    cfg = dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _mk(kind, m, params, *, paged, exact=False, **kw):
    """A backend of `kind`.  The offload engine always pages: for it
    `paged=False` means its default page settings and `paged=True` hands it
    the page settings in `kw`.  `exact` holds every expert at full
    precision, so the engine's numerics are the dense model's."""
    if kind == "dense":
        return DenseBackend(m, params, paged=paged, **kw)
    if exact:
        n = m.cfg.num_layers * m.cfg.moe.num_experts
        ecfg = EngineConfig(hi_slots=n, lo_slots=1,
                            thresholds=Thresholds(1.0, 1.0), prefetch=False)
    else:
        ecfg = EngineConfig(hi_slots=16, lo_slots=8,
                            thresholds=Thresholds(0.6, 0.9))
    if paged:
        ecfg = dataclasses.replace(
            ecfg, kv_page_size=kw.get("page_size", 64),
            kv_pages=kw.get("kv_pages"),
            prefill_chunk=kw.get("prefill_chunk", 64))
    return HobbitBackend(OffloadEngine(m, params, ecfg))


# ---------------------------------------------------------------- parity
@pytest.mark.parametrize("kind", ["dense", "hobbit"])
def test_paged_vs_dense_logits_parity(setup, kind):
    """Per-step decode logits of either backend under the paged layout
    equal the dense model's unpaged run (page size chosen so slots span
    several pages; the offload engine holds every expert at full
    precision)."""
    m, params = setup
    prompts = np.random.default_rng(0).integers(0, 128, (2, 9))
    teacher = np.random.default_rng(1).integers(0, 128, (4, 2))
    d = DenseBackend(m, params, paged=False)
    p = _mk(kind, m, params, paged=True, exact=True, page_size=4,
            prefill_chunk=5)
    d.start_batch(2, 32)
    p.start_batch(2, 32)
    lg_d, lg_p = d.prefill(prompts), p.prefill(prompts)
    np.testing.assert_allclose(lg_d, lg_p, atol=1e-4)
    for t in range(4):
        np.testing.assert_allclose(d.step(teacher[t]), p.step(teacher[t]),
                                   atol=1e-4)


@pytest.mark.parametrize("kind", ["dense", "hobbit"])
def test_paged_generate_tokens_equal(setup, kind):
    m, params = setup
    prompts = np.random.default_rng(2).integers(0, 128, (2, 7))
    res_d = generate(DenseBackend(m, params, paged=False), prompts, 6)
    res_p = generate(_mk(kind, m, params, paged=True, exact=True,
                         page_size=4, prefill_chunk=3), prompts, 6)
    np.testing.assert_array_equal(res_d.tokens, res_p.tokens)


def test_paged_decode_pallas_kernel_matches_xla_path(setup, monkeypatch):
    """Engine-level kernel parity: decode through the table-driven paged
    flash kernel (REPRO_KERNEL_MODE=pallas -> interpret mode on CPU) equals
    the gathered-oracle XLA path per step, and the dispatch counter proves
    the kernel ran."""
    m, params = setup
    prompts = np.random.default_rng(8).integers(0, 128, (2, 9))
    teacher = np.random.default_rng(9).integers(0, 128, (4, 2))
    outs = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
        be = _mk("hobbit", m, params, paged=True, page_size=4,
                 prefill_chunk=5)
        be.start_batch(2, 32)
        lgs = [be.prefill(prompts)]
        for t in range(4):
            lgs.append(be.step(teacher[t]))
        outs[mode] = np.stack(lgs)
        if mode == "pallas":
            disp = be.engine.stats()["kernel_dispatch"]
            assert disp.get("paged_flash_decode.pallas_interpret", 0) > 0
    np.testing.assert_allclose(outs["pallas"], outs["xla"], atol=1e-4)


def test_chunked_prefill_matches_oneshot(setup):
    """Admission logits are identical whether the prompt prefills in one
    chunk or many (chunk boundaries are invisible to the attention math)."""
    m, params = setup
    prompt = np.random.default_rng(3).integers(0, 128, 11)
    outs = []
    for chunk in (32, 11, 4, 3):
        be = DenseBackend(m, params, paged=True, page_size=4,
                          prefill_chunk=chunk)
        be.start_batch(1, 32)
        be.release(0)
        outs.append(be.join(0, prompt))
    ref = DenseBackend(m, params)
    ref.start_batch(1, 32)
    ref.release(0)
    lg_ref = ref.join(0, prompt)
    for lg in outs:
        np.testing.assert_allclose(lg, outs[0], atol=1e-5)
    np.testing.assert_allclose(outs[0], lg_ref, atol=1e-4)


# ------------------------------------------------------------ reclamation
def test_release_reclaims_pages_and_readmits(setup):
    """Mid-flight release returns a slot's pages to the pool and a new
    request admitted into the same slot immediately reuses them, decoding
    exactly like its isolated run."""
    m, params = setup
    rng = np.random.default_rng(4)
    pa, pb = rng.integers(0, 128, 9), rng.integers(0, 128, 6)
    be = DenseBackend(m, params, paged=True, page_size=4, kv_pages=8,
                      prefill_chunk=4)
    be.start_batch(2, 16)
    for s in (0, 1):
        be.release(s)
    assert be.kv.pages_used == 0
    be.join(0, pa)                      # 9 tokens -> 3 pages (reserve 16 -> 4)
    used_a = be.kv.pages_used
    assert used_a == 3 and be.stats()["kv_page_fraction"] == 3 / 8
    be.release(0)
    assert be.kv.pages_used == 0        # reclaimed, reservation dropped
    lg = be.join(0, pb)                 # immediate re-admission, same slot
    toks = [int(np.argmax(lg))]
    for _ in range(4):
        vec = np.zeros((2,), np.int32)
        vec[0] = toks[-1]
        lg = be.step(vec)
        toks.append(int(np.argmax(lg[0])))
    want = generate(DenseBackend(m, params), pb[None], 5, max_len=16)
    np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                  want.tokens[0, len(pb):])


def test_pool_exhaustion_raises_without_reservation():
    """ensure() without an admission reservation raises PagePoolExhausted
    instead of corrupting a neighbour's pages."""
    pool = PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=4,
                       dtype="float32", num_pages=2, page_size=4)
    pool.start(2)
    pool.ensure(0, 8)                   # slot 0 takes both pages
    with pytest.raises(PagePoolExhausted):
        pool.ensure(1, 4)
    pool.release(0)
    pool.ensure(1, 4)                   # reclaimed pages are reusable
    assert pool.pages_used == 1


def test_reservation_blocks_new_admission():
    """Admission reservations protect an in-flight request's decode budget:
    can_reserve must refuse a second request that would starve the first."""
    pool = PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=4,
                       dtype="float32", num_pages=5, page_size=4)
    pool.start(2)
    pool.reserve(0, 13)                 # 4 pages promised
    pool.ensure(0, 5)                   # only 2 drawn so far; 2 still owed
    assert not pool.can_reserve(8)      # 2 pages would overlap the promise
    assert pool.can_reserve(4)          # 1 page genuinely free
    pool.ensure(0, 13)                  # the promise is honored
    assert pool.pages_used == 4


def test_unreserved_growth_cannot_steal_reserved_pages():
    """Regression: ensure() must not hand out pages another slot's
    reservation promises — the unreserved grower raises PagePoolExhausted
    at its own call site, and the reserved slot still grows to its full
    budget afterwards (the reservation contract)."""
    pool = PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=4,
                       dtype="float32", num_pages=4, page_size=4)
    pool.start(2)
    pool.reserve(0, 16)                 # all 4 pages promised to slot 0
    with pytest.raises(PagePoolExhausted):
        pool.ensure(1, 4)               # unreserved growth would steal one
    pool.ensure(0, 16)                  # the promise is honored in full
    assert pool.pages_used == 4


def test_growth_past_own_reservation_cannot_steal():
    """A slot growing past its own reservation competes as unreserved: it
    must raise rather than take a page promised to a neighbour, and the
    neighbour's reservation stays drawable."""
    pool = PagedKVPool(num_layers=1, num_kv_heads=1, head_dim=4,
                       dtype="float32", num_pages=4, page_size=4)
    pool.start(2)
    pool.reserve(0, 8)                  # 2 pages promised to slot 0
    pool.reserve(1, 8)                  # 2 pages promised to slot 1
    pool.ensure(0, 8)                   # slot 0 draws its own 2
    with pytest.raises(PagePoolExhausted):
        pool.ensure(0, 12)              # a 3rd page would rob slot 1
    pool.ensure(1, 8)                   # slot 1's promise intact
    assert pool.pages_used == 4


# ------------------------------------------------------- scheduler behavior
@pytest.mark.parametrize("kind", ["dense", "hobbit"])
def test_exhausted_pool_queues_request_until_pages_free(setup, kind):
    """A request that does not fit the remaining pool waits in the queue
    (no crash) and is admitted as soon as a retirement frees pages; every
    request still completes with its isolated-run output."""
    m, params = setup
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, 128, 8),
                    max_new_tokens=4) for i in range(3)]
    prompts = [np.array(r.prompt) for r in reqs]
    # pool of 8 4-token pages; each request needs ceil((8+4+1)/4)=4 pages,
    # so only two fit concurrently — rid=2 must wait for a retirement
    be = _mk(kind, m, params, paged=True, page_size=4, kv_pages=8,
             prefill_chunk=4)
    srv = BatchingServer(be, max_batch=3, max_len=16)
    for r in reqs:
        srv.submit(r)
    srv.run()
    assert len(srv.completed) == 3
    first_retire = min(e[3] for e in srv.events if e[0] == "retire")
    late_admits = [e for e in srv.events if e[0] == "admit"
                   and e[3] >= first_retire]
    assert late_admits, "third request should admit only after pages freed"
    for i, p in enumerate(prompts):
        got = next(r for r in srv.completed if r.rid == i)
        want = generate(_mk(kind, m, params, paged=False), p[None], 4,
                        max_len=16)
        np.testing.assert_array_equal(got.output, want.tokens[0, len(p):])
    st = srv.stats()
    assert st["admission_wait_s"] >= st["mean_queue_wait_s"] >= 0.0
    assert st["mean_occupancy"] > 0


def test_oversized_request_raises_not_hangs(setup):
    """A request larger than the entire pool can never be served: the
    scheduler raises instead of spinning forever."""
    m, params = setup
    be = DenseBackend(m, params, paged=True, page_size=4, kv_pages=2,
                      prefill_chunk=4)
    srv = BatchingServer(be, max_batch=2, max_len=16)
    srv.submit(Request(rid=0, prompt=np.arange(10) % 128, max_new_tokens=4))
    with pytest.raises(RuntimeError, match="cannot hold"):
        srv.run()


def test_request_wider_than_page_table_rejected_cleanly(setup):
    """A request that fits the pool's page count but exceeds the per-slot
    page-table width (max_len bound) is rejected by the same clean
    RuntimeError — never a mid-run crash that loses in-flight requests."""
    m, params = setup
    # pool of 12 pages but max_len=16 -> only 4 pages per slot
    be = DenseBackend(m, params, paged=True, page_size=4, kv_pages=12,
                      prefill_chunk=4)
    srv = BatchingServer(be, max_batch=2, max_len=16)
    srv.submit(Request(rid=0, prompt=np.arange(18) % 128, max_new_tokens=4))
    with pytest.raises(RuntimeError, match="cannot hold"):
        srv.run()


def test_chunked_admission_interleaves_with_decode(setup):
    """A long prompt admitted mid-flight prefills in chunks across several
    scheduler iterations while the in-flight request keeps decoding: its
    admit->join span covers decode steps, and the decoding request's output
    is unchanged."""
    m, params = setup
    rng = np.random.default_rng(6)
    long_p = rng.integers(0, 128, 20)
    short_p = rng.integers(0, 128, 4)
    be = DenseBackend(m, params, paged=True, page_size=4, kv_pages=16,
                      prefill_chunk=4)
    srv = BatchingServer(be, max_batch=2, max_len=32, admit_k=2)
    srv.submit(Request(rid=0, prompt=short_p, max_new_tokens=10))
    srv.submit(Request(rid=1, prompt=long_p, max_new_tokens=3))
    srv.run()
    assert len(srv.completed) == 2
    ev = {(e[0], e[2]): e[3] for e in srv.events}
    # the long prompt's chunked admission spans >= 20/4 scheduler steps
    assert ev[("join", 1)] - ev[("admit", 1)] >= 4
    want = generate(DenseBackend(m, params), short_p[None], 10, max_len=32)
    got = next(r for r in srv.completed if r.rid == 0)
    np.testing.assert_array_equal(got.output, want.tokens[0, len(short_p):])


# ------------------------------------------------------- prefix sharing
def test_prefix_sharing_differential_three_ways(setup):
    """The same shared-prefix workload produces fp-identical decode logits
    through paged-with-sharing, paged-without-sharing and the dense layout:
    aliased pages, COW copies and write-dropped re-feeds are invisible to
    the math.  Covers a mid-page divergence (forces a partial-page COW) and
    a page-boundary fork (pure aliasing, no COW)."""
    m, params = setup
    rng = np.random.default_rng(10)
    base = rng.integers(0, 128, 10)         # 2 full 4-token pages + 2 extra
    # v1 extends base past its end: aliases all 10 tokens (incl. the shared
    # partial page) and its first divergent write COWs that page
    v1 = np.concatenate([base, rng.integers(0, 128, 3)])
    # v2 forks exactly at a page boundary: pure full-page aliasing, no COW
    v2 = np.concatenate([base[:8], [(base[8] + 1) % 128],
                         rng.integers(0, 128, 2)])
    teacher = rng.integers(0, 128, (4, 3))
    outs = {}
    for mode in ("sharing", "plain", "dense"):
        be = DenseBackend(m, params, paged=mode != "dense", page_size=4,
                          prefill_chunk=4, prefix_sharing=mode == "sharing")
        be.start_batch(3, 32)
        for s in range(3):
            be.release(s)
        lgs = [be.join(s, p) for s, p in enumerate((base, v1, v2))]
        for t in range(4):
            lgs.append(be.step(teacher[t]).reshape(-1))
        outs[mode] = np.concatenate([np.asarray(x).reshape(-1) for x in lgs])
        if mode == "sharing":
            st = be.kv.stats()
            # v1 aliases base whole (10); v2 its first two pages (8).  A
            # prompt diverging INSIDE the partial page's written tokens
            # would alias only the full pages (a page is aliased as a
            # unit); v1 instead extends base, so its divergence starts at
            # base's end and its first write COWs the shared partial page.
            assert st["prefix_hit_tokens"] == 10 + 8
            assert st["cow_copies"] >= 1
            assert st["aliased_page_fraction"] > 0
        elif mode == "plain":
            st = be.kv.stats()
            assert st["prefix_hit_tokens"] == 0 and st["cow_copies"] == 0
    np.testing.assert_allclose(outs["sharing"], outs["plain"], atol=1e-4)
    np.testing.assert_allclose(outs["sharing"], outs["dense"], atol=1e-4)


def test_identical_prompt_aliases_whole_prefix(setup):
    """Length-0 divergence: an identical prompt aliases every page (full
    pages AND the trailing partial), re-prefills nothing but the final
    token's logits, and pays its first COW only when decode appends into
    the shared partial page.  Logits stay equal to the unshared run."""
    m, params = setup
    rng = np.random.default_rng(11)
    pa = rng.integers(0, 128, 10)
    teacher = rng.integers(0, 128, (3, 2))
    runs = {}
    for sharing in (True, False):
        be = DenseBackend(m, params, paged=True, page_size=4,
                          prefill_chunk=4, prefix_sharing=sharing)
        be.start_batch(2, 32)
        for s in range(2):
            be.release(s)
        lg0, lg1 = be.join(0, pa), be.join(1, pa)
        np.testing.assert_allclose(lg0, lg1, atol=1e-5)
        if sharing:
            assert be.kv.stats()["prefix_hit_tokens"] == len(pa)
            assert be.kv.pages_used == 3        # not 6: all 3 pages shared
            assert be.kv.aliased_pages == 3
        steps = [be.step(teacher[t]) for t in range(3)]
        if sharing:
            # both slots' first append hits the shared partial page: one
            # COW (the other writer is by then the sole referent)
            assert be.kv.stats()["cow_copies"] == 1
        runs[sharing] = np.stack([np.asarray(s) for s in steps])
    np.testing.assert_allclose(runs[True], runs[False], atol=1e-4)


def test_prefix_sharing_pallas_kernel_parity(setup, monkeypatch):
    """Decode through the Pallas paged flash kernel over *aliased* page
    tables (two slots pointing at shared physical pages) matches the XLA
    gather path step for step — sharing needs no kernel changes."""
    m, params = setup
    rng = np.random.default_rng(12)
    pa = rng.integers(0, 128, 9)
    pb = np.concatenate([pa[:8], [(pa[8] + 1) % 128], rng.integers(0, 128, 2)])
    teacher = rng.integers(0, 128, (3, 2))
    outs = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
        be = _mk("hobbit", m, params, paged=True, page_size=4,
                 prefill_chunk=5)
        be.start_batch(2, 32)
        for s in range(2):
            be.release(s)
        lgs = [be.join(0, pa), be.join(1, pb)]
        for t in range(3):
            lgs.append(be.step(teacher[t]).reshape(-1))
        outs[mode] = np.concatenate([np.asarray(x).reshape(-1) for x in lgs])
        st = be.engine.stats()
        assert st["prefix_hit_tokens"] > 0, "workload must actually share"
        if mode == "pallas":
            disp = st["kernel_dispatch"]
            assert disp.get("paged_flash_decode.pallas_interpret", 0) > 0
    np.testing.assert_allclose(outs["pallas"], outs["xla"], atol=1e-4)


@pytest.mark.parametrize("kind", ["dense", "hobbit"])
def test_scheduler_shared_prefix_outputs_unchanged(setup, kind):
    """Continuous batching with a common system prompt: every request's
    output equals its isolated dense run whether sharing is on or off, and
    the sharing run reports prefix hits (admit_k=1 so each prompt is in
    the trie before the next admission matches it)."""
    m, params = setup
    rng = np.random.default_rng(13)
    sys_prompt = rng.integers(0, 128, 8)
    prompts = [np.concatenate([sys_prompt, rng.integers(0, 128, 3 + i)])
               for i in range(3)]
    for sharing in (True, False):
        be = _mk(kind, m, params, paged=True, page_size=4, prefill_chunk=4)
        if kind == "dense":
            be.prefix_sharing = sharing
        else:
            be.engine.ecfg = dataclasses.replace(
                be.engine.ecfg, prefix_sharing=sharing)
        srv = BatchingServer(be, max_batch=3, max_len=32, admit_k=1)
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, prompt=p, max_new_tokens=4))
        srv.run()
        assert len(srv.completed) == 3
        hits = srv.stats()["backend"].get("prefix_hit_tokens", 0)
        assert (hits >= 2 * len(sys_prompt)) == sharing
        for i, p in enumerate(prompts):
            got = next(r for r in srv.completed if r.rid == i)
            want = generate(_mk(kind, m, params, paged=False), p[None], 4,
                            max_len=32)
            np.testing.assert_array_equal(got.output,
                                          want.tokens[0, len(p):])


def test_second_release_of_shared_slot_is_noop(setup):
    """Model-level double release: releasing a retired slot again must not
    free a sharer's still-referenced pages out from under it (the logits of
    the surviving slot are unchanged afterwards)."""
    m, params = setup
    rng = np.random.default_rng(14)
    pa = rng.integers(0, 128, 9)
    be = DenseBackend(m, params, paged=True, page_size=4, prefill_chunk=4)
    be.start_batch(2, 32)
    for s in range(2):
        be.release(s)
    be.join(0, pa)
    lg1 = be.join(1, pa)                # aliases slot 0's pages
    be.release(0)
    used = be.kv.pages_used
    before = be.kv.refcount.copy()
    be.release(0)                       # double release: clean no-op
    assert be.kv.pages_used == used
    np.testing.assert_array_equal(be.kv.refcount, before)
    # the sharer still decodes correctly over its (now exclusive) pages
    toks = [int(np.argmax(lg1))]
    vec = np.zeros((2,), np.int32)
    for _ in range(3):
        vec[1] = toks[-1]
        lg = be.step(vec)
        toks.append(int(np.argmax(lg[1])))
    want = generate(DenseBackend(m, params), pa[None], 4, max_len=32)
    np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                  want.tokens[0, len(pa):])


def test_backend_stats_have_kv_fields(setup):
    """kv_pages_used / kv_pages_total / kv_page_fraction are part of the
    uniform stats contract on both layouts (zeros when dense)."""
    m, params = setup
    d = DenseBackend(m, params)
    d.start_batch(1, 8)
    s = d.stats()
    assert s["kv_pages_total"] == 0 and s["kv_page_fraction"] == 0.0
    e = _mk("hobbit", m, params, paged=True, page_size=4)
    e.start_batch(1, 8)
    e.prefill(np.random.default_rng(7).integers(0, 128, (1, 5)))
    s = e.stats()
    assert s["kv_pages_total"] == 2 and s["kv_pages_used"] == 2
    assert s["kv_page_fraction"] == 1.0
