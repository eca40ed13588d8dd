"""The trace reduction: interval arithmetic on hand-made intervals, and the
whole reduction on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, recorded by ``record_trace.py``: inside a
``bench.window`` span, three ``bench.step`` spans, each a jitted matmul, a
20 ms sleep inside a ``bench.sleep`` span and a jitted add, with 10 ms
outside any step after each).  On the chip that recorded it the device
plane's clock ran about 3.2 ms behind the host plane's (each program
starts 3.0-3.3 ms before the host call that launched it), so gaps here are
placed to within a few ms; a decode window's gaps last hundreds."""

import pathlib

import pytest

import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert xplane.union(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert xplane.covered(iv) == pytest.approx(3.0)
    assert xplane.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert xplane.gaps(iv, 0.5, 3.5) == [(2.0, 3.0)]


def test_label_gap_innermost_span():
    spans = [("bench.step", 0.0, 10.0), ("bench.sleep", 4.0, 6.0)]
    assert xplane.label_gap((4.5, 5.5), spans) == "bench.sleep"
    assert xplane.label_gap((1.0, 2.0), spans) == "bench.step"
    assert xplane.label_gap((11.0, 12.0), spans) == xplane.HOST_FALLBACK


@pytest.fixture(scope="module")
def small():
    return xplane.Trace.load(str(DATA))


def _raw(small):
    lo, hi = small.window()
    return lo, hi, [(n, s, e) for evs in small.ops.values()
                    for n, _, s, e in evs if lo <= s < hi]


def test_busy_union_and_idle_share(small):
    lo, hi, ev = _raw(small)
    assert ev, "the recorded trace has no device operation in its window"
    # brute force: a 10 us grid over the window
    step = 1e-5
    n = int((hi - lo) / step)
    busy = sum(any(s <= lo + (i + 0.5) * step < e for _, s, e in ev)
               for i in range(n)) * step
    assert small.busy_s() == pytest.approx(busy, abs=5 * step * len(ev) + 1e-4)
    # the sleeps (3 x 30 ms) leave most of the window idle
    assert 0.0 < small.busy_s() < (hi - lo) - 0.08


def test_per_name_device_time(small):
    lo, hi, ev = _raw(small)
    names = {n for n, _, _ in ev}
    total = 0.0
    for name in names:
        t, count = small.op_time("^" + __import__("re").escape(name) + "$")
        want = [e - s for n, s, e in ev if n == name]
        assert count == len(want)
        assert t == pytest.approx(sum(want))
        total += t
    assert sum(t for _, t in small.top_ops(len(names))) == pytest.approx(total)


def test_gap_attribution(small):
    gaps = small.idle_gaps(6)
    # the three 20 ms sleeps are the longest gaps, named by the innermost
    # span, bench.sleep (not bench.step); the 10 ms pauses between steps
    # fall in no span
    assert [g[0] for g in gaps[:3]] == ["bench.sleep"] * 3
    assert all(0.015 < g[1] < 0.025 for g in gaps[:3])
    assert [g[0] for g in gaps[3:]] == [xplane.HOST_FALLBACK] * 3
    assert all(0.008 < g[1] < 0.016 for g in gaps[3:])
