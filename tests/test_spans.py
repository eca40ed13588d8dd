"""Spans and counters inside the decode step: the engine's `hobbit.*` spans
(one root per step, each layer span once per MoE layer, children inside
their parent), the counters that ride with them (overflow and commit bytes
shipped, device->host reads, served precisions), the scheduler's spans, the
engine's program names, and the spans on a profiler trace's host plane."""

import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import EngineConfig
from repro.core.engine import SPANS, _expert_refs
from repro.core.scoring import (PREC_HI, PREC_LO, PREC_SKIP,
                                precision_decisions)
from repro.core.spans import Spans
from repro.models import build_model
from repro.serving.api import BackendConfig, make_backend
from repro.serving.batching import BatchingServer, Request

VOCAB = 128
STEPS = 3
LAYER_SPANS = [n for n in SPANS if n.startswith("hobbit.layer.")]
# the root's direct children
CHILDREN = ["hobbit.decode.prologue", *LAYER_SPANS, "hobbit.decode.logits"]


def _model(dtype):
    cfg = smoke_variant(get_config("mixtral-8x7b"), layers=2, d_model=64,
                        vocab=VOCAB)
    cfg = dataclasses.replace(
        cfg, dtype=dtype,
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def setup():
    return _model("float32")


def _backend(setup, hi_slots=4, lo_slots=4, **ecfg):
    m, params = setup
    return make_backend(BackendConfig(
        kind="hobbit", paged=True, page_size=4, prefill_chunk=8,
        engine=EngineConfig(hi_slots=hi_slots, lo_slots=lo_slots, **ecfg)),
        m, params)


def _decode(be, steps=STEPS, batch=2, seed=0):
    """Prefill `batch` rows, then decode `steps` greedy steps; returns the
    stats before and after the decode steps."""
    rng = np.random.default_rng(seed)
    be.start_batch(batch, 48)
    lg = be.prefill(rng.integers(0, VOCAB, (batch, 6)))
    before = be.stats()
    for _ in range(steps):
        lg = be.step(np.argmax(lg, -1).astype(np.int32))
    return before, be.stats()


def _delta(a, b, *path):
    for p in path:
        a, b = a[p], b[p]
    return b - a


@pytest.fixture(scope="module")
def run(setup):
    be = _backend(setup)
    before, after = _decode(be)
    yield be, before, after
    be.close()


def test_one_root_per_step_and_each_layer_span_once_per_moe_layer(run):
    be, before, after = run
    layers = be.engine.num_moe_layers
    n = {name: _delta(before, after, "spans", name, "n") for name in SPANS}
    assert n["hobbit.decode"] == STEPS
    assert n["hobbit.decode.prologue"] == STEPS
    assert n["hobbit.decode.logits"] == STEPS
    for name in LAYER_SPANS:
        assert n[name] == STEPS * layers, name
    assert n["hobbit.prefill.chunk"] == 0
    # every name is declared before it first opens
    assert set(before["spans"]) == set(SPANS)


def test_children_never_exceed_their_parent(run):
    _, before, after = run
    s = {name: _delta(before, after, "spans", name, "s") for name in SPANS}
    assert 0.0 < sum(s[c] for c in CHILDREN) <= s["hobbit.decode"]
    assert s["hobbit.stage.commit"] <= s["hobbit.layer.stage_wait"]
    assert all(v >= 0.0 for v in s.values())


@pytest.mark.parametrize("dtype,hi_slots,lo_slots", [
    ("float32", 2, 1), ("bfloat16", 2, 1),
    # every expert of every layer fits: no reload, nothing crosses
    ("float32", 16, 16)])
def test_overflow_bytes_are_the_reloaded_experts(dtype, hi_slots, lo_slots):
    be = _backend(_model(dtype), hi_slots=hi_slots, lo_slots=lo_slots)
    eng = be.engine
    assert eng.num_moe_layers * eng.cfg.moe.num_experts <= 16
    reloaded = []
    plan_layer = eng._plan_layer

    def recording(*args):
        plan, ovf = plan_layer(*args)
        reloaded.extend(prec for prec, _, _ in ovf)
        return plan, ovf

    eng._plan_layer = recording
    before, after = _decode(be)
    assert len(reloaded) == _delta(before, after, "union_reloads")
    assert (len(reloaded) > 0) == (hi_slots < 16)
    # a reloaded hi expert crosses as its host copy, in the model's dtype, a
    # lo one as stored
    hi, lo = eng.storage_hi[0], eng.storage_lo[0]
    nbytes = {PREC_HI: hi["wi"][0].nbytes + hi["wo"][0].nbytes,
              PREC_LO: sum(a.nbytes for q in (lo["wi"], lo["wo"])
                           for a in (q.data[0], q.scale[0]))}
    assert hi["wi"].dtype == hi["wo"].dtype == np.dtype(dtype)
    assert (_delta(before, after, "overflow_shipped_bytes")
            == sum(nbytes[p] for p in reloaded))
    be.close()


def test_commit_bytes_count_what_the_commits_hand_over(setup):
    be = _backend(setup)
    eng = be.engine
    be.start_batch(1, 16)
    s0 = eng.stats()
    hi = eng.storage_hi[0]
    lo = eng.storage_lo[0]
    eng._fetch_many([(0, 0, PREC_HI, 0), (0, 1, PREC_LO, 0)])
    want = (hi["wi"][0].nbytes + hi["wo"][0].nbytes + lo["wi"].data[1].nbytes
            + lo["wi"].scale[1].nbytes + lo["wo"].data[1].nbytes
            + lo["wo"].scale[1].nbytes)
    s1 = eng.stats()
    assert _delta(s0, s1, "commit_shipped_bytes") == want
    assert _delta(s0, s1, "spans", "hobbit.stage.commit", "n") == 1
    be.close()


def _bits(a):
    a = np.ascontiguousarray(a)
    return a.view(np.uint8).tobytes(), a.dtype, a.shape


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_hi_host_copy_is_the_models_dtype_bit_for_bit(dtype):
    # the host keeps hi in the pool's dtype: bf16 ships the 16-bit bytes the
    # link model counts, float32 stays float32, and a commit lands the
    # device leaf's values bit for bit either way
    m, params = _model(dtype)
    be = _backend((m, params))
    eng = be.engine
    li, mi, e = eng.moe_layers[0], 0, 1
    experts, lead = _expert_refs(m.cfg, params)[li]
    hi = eng.storage_hi[mi]
    for name in ("wi", "wo"):
        assert hi[name].dtype == eng.dtype == np.dtype(dtype)
        assert _bits(hi[name][e]) == _bits(experts[name][(*lead, e)])
    be.start_batch(1, 16)
    s0 = eng.stats()
    eng._fetch_many([(mi, e, PREC_HI, 0)])
    s1 = eng.stats()
    for name in ("wi", "wo"):
        assert _bits(eng.pool_hi[name][0]) == _bits(hi[name][e])
    shipped = _delta(s0, s1, "commit_shipped_bytes")
    assert shipped == hi["wi"][e].nbytes + hi["wo"][e].nbytes
    # one definition of a hi expert's bytes where the model is 16-bit; a
    # float32 model ships its 4-byte values, as before
    assert shipped == eng.expert_bytes[PREC_HI] * np.dtype(dtype).itemsize // 2
    be.close()


def test_host_syncs_are_two_plus_three_per_moe_layer(run):
    be, before, after = run
    layers = be.engine.num_moe_layers
    assert _delta(before, after, "host_syncs") == STEPS * (2 + 3 * layers)


def test_served_precisions_follow_eq2_on_the_traced_gates(setup):
    # FIFO staging never downgrades at issue time, so what was served is
    # the rule applied to the gates the trace recorded
    be = _backend(setup, streams=1, ordered=True)
    eng = be.engine
    before, after = _decode(be, seed=1)
    assert _delta(before, after, "precision_downgrades") == 0
    served = {p: _delta(before, after, f"served_{n}")
              for p, n in ((PREC_HI, "hi"), (PREC_LO, "lo"),
                           (PREC_SKIP, "skip"))}
    k = eng.cfg.moe.top_k
    assert sum(served.values()) == 2 * STEPS * eng.num_moe_layers * k
    want = {PREC_HI: 0, PREC_LO: 0, PREC_SKIP: 0}
    for row in eng.trace:
        for tl in row:
            for d in precision_decisions(np.asarray(tl.gate_vals),
                                         eng.loader.th):
                want[int(d)] += 1
    assert served == want
    be.close()


def test_gating_s_is_the_gate_span_total(run):
    _, _, after = run
    assert after["gating_s"] == after["spans"]["hobbit.layer.gate"]["s"]
    assert after["gating_s"] > 0.0


def test_step_programs_carry_hobbit_names(run):
    be, _, _ = run
    cache = be.engine._jit_cache
    for key in ("gate", "residual_add", "grouped_ffn", ("scatter", 2)):
        assert cache[key].__name__.startswith("hobbit_"), key
    # the benchmark finds the grouped FFN program by this pattern
    assert "grouped_ffn" in cache["grouped_ffn"].__name__


def test_scheduler_spans_count_admission_and_sampling(setup):
    be = _backend(setup)
    rng = np.random.default_rng(3)
    srv = BatchingServer(be, max_batch=2, max_len=48, admit_k=2,
                         policy="fifo")
    for rid in range(3):
        srv.submit(Request(rid=rid, prompt=rng.integers(0, VOCAB, 5),
                           max_new_tokens=3))
    srv.run()
    st = srv.stats()
    sp = st["spans"]
    assert set(sp) == {"hobbit.sched.admit", "hobbit.sched.sample"}
    # one sample per prefill chunk that finished an admission and per step
    steps = st["backend"]["spans"]["hobbit.decode"]["n"]
    assert sp["hobbit.sched.sample"]["n"] == steps + 3
    assert sp["hobbit.sched.admit"]["n"] >= 1
    srv.close()


def test_span_names_are_declared_up_front():
    sp = Spans(["a", "b"])
    assert sp.totals() == {"a": {"s": 0.0, "n": 0}, "b": {"s": 0.0, "n": 0}}
    with sp("a", layer=1):
        pass
    with sp.step("b", 7):
        pass
    t = sp.totals()
    assert t["a"]["n"] == 1 and t["b"]["n"] == 1
    with pytest.raises(KeyError):
        sp("c")


def test_profiler_trace_holds_the_spans_on_the_host_plane(setup, tmp_path):
    from jax.profiler import ProfileData
    be = _backend(setup)
    _decode(be, steps=1)           # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("outer"):
        be.step(np.zeros(2, np.int32))
    jax.profiler.stop_trace()
    be.close()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    events = [(ev.name, ev.start_ns, ev.end_ns)
              for p in host for line in p.lines for ev in line.events]
    outer = [(s, e) for n, s, e in events if n == "outer"]
    spans = [(n, s, e) for n, s, e in events if n.startswith("hobbit.")]
    assert len(outer) == 1
    lo, hi = outer[0]
    names = {n for n, _, _ in spans}
    assert {"hobbit.decode", "hobbit.layer.gate",
            "hobbit.layer.ship_overflow", "hobbit.decode.logits"} <= names
    assert all(lo <= s and e <= hi for _, s, e in spans)
    root = [(s, e) for n, s, e in spans if n == "hobbit.decode"]
    assert len(root) == 1
    assert all(root[0][0] <= s and e <= root[0][1]
               for n, s, e in spans if n.startswith("hobbit.layer."))
