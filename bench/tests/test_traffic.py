"""The traffic generator: every seed sends the same set of lengths in
another order, with token ids from the seed."""

import numpy as np
import pytest

import traffic


def _lengths(mix, seed):
    reqs = [r for c in traffic.generate(mix, 1000, seed) for r in c]
    return ([len(r["prompt"]) for r in reqs], [r["max_new"] for r in reqs],
            reqs)


def test_same_lengths_other_order():
    mix = traffic.load_mix("chat-2")
    p1, o1, r1 = _lengths(mix, 2**33 + 1)
    p2, o2, r2 = _lengths(mix, 7)
    assert sorted(p1) == sorted(p2) and sorted(o1) == sorted(o2)
    assert p1 != p2
    assert min(p1) >= 64 and max(p1) <= 1024
    assert min(o1) >= 64 and max(o1) <= 256
    assert not np.array_equal(r1[0]["prompt"][:64], r2[0]["prompt"][:64])
    # the same seed gives the same inputs
    p3, o3, r3 = _lengths(mix, 2**33 + 1)
    assert p3 == p1 and o3 == o1
    assert all(np.array_equal(a["prompt"], b["prompt"]) for a, b in zip(r1, r3))


def test_quantiles():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64,
            "max": 1024}
    got = traffic.quantile_lengths(spec, 101)
    assert got[50] == 256 and (np.diff(got) >= 0).all()
    assert list(traffic.quantile_lengths({"dist": "uniform", "min": 1,
                                          "max": 4}, 4)) == [1, 2, 3, 4]
    assert traffic.max_context(traffic.load_mix("prompt-only")) == 2049


def test_refuses_unknown_keys(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text('{"clients": 1, "rate": 2.0}')
    monkeypatch.setattr(traffic, "HERE", tmp_path)
    with pytest.raises(ValueError, match="rate"):
        traffic.load_mix("x")
