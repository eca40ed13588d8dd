"""The output check: verdicts against limits, the router near-tie rule,
the sample of compared requests, and the float8 control coming out not
correct on a tiny served run."""

import numpy as np
import pytest

import check
from conftest import LIMITS, run_tiny


def test_verdict():
    limits = {"token_gap": 0.5, "route_gap": 0.2}
    ok, rows = check.verdict({"token_gap": 0.1, "route_gap": 0.05,
                              "requests": 2}, limits, failed=0)
    assert ok and [r["name"] for r in rows] == ["token_gap", "route_gap"]
    assert not check.verdict({"token_gap": 0.6, "route_gap": 0.05,
                              "requests": 2}, limits, 0)[0]
    assert not check.verdict({"token_gap": 0.1, "route_gap": 0.3,
                              "requests": 2}, limits, 0)[0]
    # a non-finite logits row fails the run; so does nothing compared, and
    # a number without a limit
    assert not check.verdict({"token_gap": 0.1, "requests": 2}, limits, 1)[0]
    assert not check.verdict({"token_gap": 0.0, "requests": 0}, limits, 0)[0]
    assert not check.verdict({"token_gap": 0.1, "requests": 2}, {}, 0)[0]
    # a prompt-only run has no decode position, so no route_gap
    ok, rows = check.verdict({"token_gap": 0.1, "route_gap": None,
                              "requests": 2}, limits, 0)
    assert ok and [r["name"] for r in rows] == ["token_gap"]


def test_rule_precision_is_the_programs():
    """The check's copy of Eq. 2 decides as the program's rule does."""
    from repro.core.scoring import Thresholds, precision_decisions
    g = np.random.default_rng(5).random((500, 3)) ** 3
    for th in ((0.6, 0.9), (0.3, 0.5)):
        want = precision_decisions(g, Thresholds(*th))
        assert (check.rule_precision(g, *th) == want).all()


def test_lo_excess_counts_below_rule():
    ref = type("R", (), {"th": (0.6, 0.9)})()
    # one layer, two positions, experts 0 and 1; gates 0.5/0.5 put rank 1
    # at score 0.5 (hi), 0.8/0.2 at 0.8 (lo)
    rls = [np.log(np.array([[0.5, 0.5, 1e-9], [0.8, 0.2, 1e-9]]))]
    experts = np.array([[[0, 1]], [[0, 1]]])
    rule = np.array([[[0, 0]], [[0, 1]]])
    assert check._below_rule(ref, rls, experts, rule, np.arange(2)) == (0, 4)
    served = np.array([[[0, 1]], [[0, 2]]])       # one lo, one skip too many
    assert check._below_rule(ref, rls, experts, served, np.arange(2)) == (2, 4)
    # served above the rule is not below it
    assert check._below_rule(ref, rls, experts, np.zeros_like(rule),
                             np.arange(2)) == (0, 4)


def test_near_tie():
    s = np.array([3.0, 1.0, 0.98, 0.5])
    assert check._near_tie(s, 2).tolist() == [0, 2]
    assert check._near_tie(np.array([3.0, 1.0, 0.5, 0.4]), 2) is None


def test_sample_keeps_longest():
    done = [{"prompt": np.zeros(n), "served": [1]} for n in range(10, 60)]
    done.append({"prompt": np.zeros(5), "served": []})     # never served
    got = check.sample_requests(done, 8, seed=3)
    assert len(got) == 8 and got[0] is done[49]
    assert got == check.sample_requests(done, 8, seed=3)
    assert all(len(r["served"]) for r in got)


@pytest.mark.parametrize("kind", ["chat", "single-user", "prompt"])
def test_control_is_not_correct(kind):
    """The reference in float8 put in the program's place, on the same
    prompts and tokens as a sound tiny run, fails the limits the run itself
    passes."""
    out = run_tiny(kind, 2**32 + 11, control=True)
    assert out["correct"], out["compared"]
    ok, rows = check.verdict(dict(out["control"], requests=1),
                             check.load_limits(LIMITS), failed=0)
    assert not ok, rows
