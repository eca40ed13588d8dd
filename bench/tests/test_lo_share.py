"""The per-step precision record and ``lo_share``, from a hand-made engine
trace: the program's rule decides hi / lo / skip from the gates, and a
staging downgrade turns a hi decision lo.  Also the window's end: it holds
whole calls only."""

import time
import types

import numpy as np
import pytest

import cell
from cell import Proxy, Run
from repro.core.scoring import Thresholds

# normalized rank-0 gate 0.5 -> both hi; 0.7 -> rank 1 lo; 0.95 -> skip
GATES = {0: [0.25, 0.25], 1: [0.35, 0.15], 2: [0.57, 0.03]}


class FakeScheduler:
    def __init__(self, downgraded):
        self.downgraded = downgraded

    def serves_lo_downgrade(self, layer, expert):
        return (layer, expert) in self.downgraded


class FakeBackend:
    """One decode step of two live rows over two MoE layers, the way the
    engine appends it to ``engine.trace``, asking the staging engine about
    hi copies it does not hold."""

    def __init__(self):
        self.model = None
        eng = types.SimpleNamespace()
        eng.active = np.array([True, True])
        eng.trace = []
        eng.loader = types.SimpleNamespace(th=Thresholds(0.6, 0.9))
        eng.cfg = types.SimpleNamespace(moe=types.SimpleNamespace(top_k=2))
        eng.scheduler = FakeScheduler({(1, 3)})
        self.engine = eng
        self.step_s = 0.0

    def step(self, tokens):
        time.sleep(self.step_s)
        eng = self.engine
        rows = []
        for r in (0, 1):
            layers = []
            # row r routes layer 0 to experts 0, 1 and layer 1 to 3, 2
            for mi, (experts, gate) in enumerate(
                    (([0, 1], GATES[r]), ([3, 2], GATES[r + 1]))):
                for e in experts:
                    eng.scheduler.serves_lo_downgrade(mi, e)
                layers.append(types.SimpleNamespace(
                    experts=experts, gate_vals=np.asarray(gate, np.float32)))
            rows.append(layers)
        eng.trace.extend(rows)
        return np.zeros((2, 8), np.float32)

    def stats(self):
        return {}


def _run():
    mix = {"warmup": {}}
    run = Run(mix, [[], []], seconds=60.0, trace_dir=None, chunk=64)
    for rid, slot in ((0, 0), (1, 1)):
        run.reqs[rid] = cell.Req(rid=rid, client=slot,
                                 prompt=np.zeros(10, np.int32), max_new=4)
        run.slot_rid[slot] = rid
    return run


def test_record_and_lo_share():
    run = _run()
    proxy = Proxy(FakeBackend(), run)
    proxy.step(np.array([5, 6], np.int32))
    r0, r1 = run.reqs[0].records[0], run.reqs[1].records[0]
    # row 0: layer 0 gates 0.5/0.5 -> hi hi; layer 1 expert 3 is rank 0
    # (always hi) but downgraded by staging -> lo, gates 0.7 -> rank 1 lo
    assert r0["experts"].tolist() == [[0, 1], [3, 2]]
    assert r0["prec"].tolist() == [[0, 0], [1, 1]]
    # row 1: layer 0 gates 0.7 -> hi lo; layer 1 gates 0.95 -> hi (but
    # downgraded) and skip
    assert r1["prec"].tolist() == [[0, 1], [1, 2]]
    assert run.reqs[0].served == [5] and run.reqs[1].served == [6]
    ctx = cell.Ctx(calls=[dict(c, in_win=True) for c in run.calls])
    share = cell.load_reader("lo_share")(ctx)
    # 8 routed computations: 3 hi, 4 lo, 1 skip
    assert share == 100.0 * 5 / 8


def test_lo_share_needs_decode_steps():
    ctx = cell.Ctx(calls=[])
    assert cell.load_reader("lo_share")(ctx) is None


def test_window_holds_whole_calls():
    """A step that begins inside the window and ends after its seconds is
    in the window, which then ends where that step ended; the next call
    closes it."""
    run = _run()
    run.seconds = 0.05
    backend = FakeBackend()
    backend.step_s = 0.08
    proxy = Proxy(backend, run)
    proxy.step(np.array([5, 6], np.int32))
    assert run.phase == "window" and run.calls[-1]["in_win"]
    with pytest.raises(cell.WindowClosed):
        proxy.step(np.array([7, 8], np.int32))
    assert run.t_end == run.calls[-1]["t1"]
    assert run.t_end - run.t_open >= 0.08
    ctx = cell.Ctx(calls=run.calls, seconds=run.t_end - run.t_open)
    assert cell.load_reader("decode_tok_s")(ctx) == 2 / (run.t_end - run.t_open)
