"""The comparison that decides ``correct``.

For each compared request the reference runs its prompt followed by the
tokens the server produced.  The numbers in ``COMPARED`` are held to the
limits in ``bench/limits/<cell>.json``; the others are read and reported:

token_gap   the widest gap, in logits, by which a served token's reference
            logit lies below the reference's best logit at that position.
            Under greedy decoding a correct server picks the reference's
            best up to rounding.  At a first token (made by the prefill,
            whose routing is not recorded) a router near-tie (the k-th and
            (k+1)-th router logits within TIE_TOL) is rounding too: the
            gap there is the smallest over the routings such ties allow.
route_gap   at decode positions the record fixes the experts the reference
            uses; this is the widest amount, in router logits, by which a
            recorded expert lies below the reference router's k-th best.
lo_excess   at decode positions, the share (%) of routed computations (row x
            MoE layer x rank) served in a lower precision than HOBBIT's rule
            gives on the reference's own gates: Eq. 2's unimportance score
            (the normalized gate mass of the experts ranked above) at most
            T1 is hi, at most T2 lo, above T2 skipped, rank 0 always hi, with
            the configuration's (T1, T2).  Only the staging engine's link
            downgrades and gates within rounding of a threshold put the
            program above 0; a change that serves more experts lo or skips
            more of them reads higher.

The control (``fp8=True``) stands in the program's place at the same
positions: its token is its own argmax, its experts its own top-k, every
expert served in float8 (below hi), and the same numbers are read for it
against the float32 reference.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional

import numpy as np

from reference import HI, LO, SKIP

HERE = pathlib.Path(__file__).resolve().parent
# router-logit near-tie at the top-k boundary that the prefill's rounding
# can flip: the program's gate logits differ from float32 by about 1e-2 at
# most (bf16 hidden states, 2^-8 relative), so 0.05 holds every flip and
# still branches at fewer than one position in ten
TIE_TOL = 0.05
# the numbers held to limits; lo_excess is read and reported (the result
# line's "check") until chip runs give the readings its limit is set from
COMPARED = ("token_gap", "route_gap")


def load_limits(cell: str) -> Dict[str, float]:
    path = HERE / "limits" / f"{cell}.json"
    return json.loads(path.read_text())["limits"] if path.exists() else {}


def _inputs(req: Dict):
    """(input tokens, positions of the served tokens, forced routing)."""
    prompt = np.asarray(req["prompt"], np.int32)
    served = list(req["served"])
    n_dec = len(served) - 1
    p = len(prompt)
    tokens = np.concatenate([prompt, np.asarray(served[:n_dec], np.int32)])
    rows = np.arange(p - 1, p - 1 + len(served))
    recs = req["records"][:n_dec]
    forced = None
    if n_dec:
        forced = {"pos": np.arange(p, p + n_dec),
                  "experts": np.stack([r["experts"] for r in recs]),
                  "prec": np.stack([r["prec"] for r in recs])}
    return tokens, rows, forced


def _near_tie(s: np.ndarray, k: int):
    """The top-k set with the k-th expert swapped for the (k+1)-th, when
    their router logits are within TIE_TOL, else None."""
    order = np.argsort(-s, kind="stable")
    if s[order[k - 1]] - s[order[k]] >= TIE_TOL:
        return None
    alt = order[:k].copy()
    alt[k - 1] = order[k]
    return alt


def _first_token_logits(ref, prompt, base_rls, base_lg, k) -> List[np.ndarray]:
    """Logits at the last prompt position for the baseline and for every
    routing its router near-ties allow, layer by layer."""
    p, n_layers = len(prompt), len(base_rls)
    leaves = [base_lg]

    def explore(fixed: Dict[int, np.ndarray], rls, start: int):
        for li in range(start, n_layers):
            alt = _near_tie(rls[li][p - 1], k)
            if alt is None:
                continue
            f = dict(fixed)
            f[li] = alt
            ex = np.full((1, n_layers, k), -1, np.int32)
            mask = np.zeros((1, n_layers), bool)
            for lj, a in f.items():
                ex[0, lj], mask[0, lj] = a, True
            lg, rls2, _ = ref.forward(prompt, {
                "pos": np.asarray([p - 1]), "experts": ex,
                "prec": np.full((1, n_layers, k), HI, np.int32),
                "mask": mask}, rows=[p - 1])
            leaves.append(lg[0])
            explore(f, rls2, li + 1)

    explore({}, base_rls, 0)
    return leaves


def _route_gap(rls, chosen_at, pos, k) -> float:
    """Widest amount by which an expert in ``chosen_at`` (n, L, k) lies
    below the router's k-th best at ``pos``."""
    worst = 0.0
    for li, rl in enumerate(rls):
        s = rl[pos]                                       # (n, E)
        kth = -np.sort(-s, axis=1)[:, k - 1]
        got = np.take_along_axis(s, chosen_at[:, li], axis=1).min(axis=1)
        worst = max(worst, float(np.max(kth - got, initial=0.0)))
    return worst


def rule_precision(gates: np.ndarray, t1: float, t2: float) -> np.ndarray:
    """HOBBIT's precision per routed expert (..., k), in the order given,
    from its gates (Eq. 2; rank 0 always hi)."""
    g = np.abs(np.asarray(gates, np.float64))
    g = g / np.maximum(g.sum(axis=-1, keepdims=True), 1e-12)
    order = np.argsort(-g, axis=-1, kind="stable")
    cum = np.cumsum(np.take_along_axis(g, order, axis=-1), axis=-1)
    score = cum - np.take_along_axis(g, order, axis=-1)
    ranked = np.where(score <= t1, HI, np.where(score <= t2, LO, SKIP))
    ranked[..., 0] = HI
    out = np.empty_like(ranked)
    np.put_along_axis(out, order, ranked, axis=-1)
    return out


def _below_rule(ref, rls, experts, prec, pos) -> (int, int):
    """(computations served below the rule on the reference's gates, all
    computations) at decode positions ``pos``; experts, prec (n, L, k)."""
    below = 0
    for li, rl in enumerate(rls):
        z = rl[pos] - rl[pos].max(axis=1, keepdims=True)
        gates = np.take_along_axis(np.exp(z), experts[:, li], axis=1)
        below += int((prec[:, li] > rule_precision(gates, *ref.th)).sum())
    return below, int(prec.size)


def compare(ref, reqs: List[Dict], ctl=None) -> Dict:
    """Numbers for the program's served tokens (and the control's, with
    ``ctl``).  reqs: [{"prompt", "served", "records": [{"experts" (L, k),
    "prec" (L, k)}]}]."""
    k = ref.k
    out = {"token_gap": 0.0, "route_gap": None, "lo_excess": None,
           "tokens": 0, "requests": 0}
    below = {"": [0, 0], "ctl_": [0, 0]}
    if ctl is not None:
        out.update(ctl_token_gap=0.0, ctl_route_gap=None, ctl_lo_excess=None)
    for req in reqs:
        if not len(req["served"]):
            continue
        tokens, rows, forced = _inputs(req)
        lg, rls, _ = ref.forward(tokens, forced, rows=rows)
        p = len(req["prompt"])
        first = _first_token_logits(ref, tokens[:p], rls, lg[0], k)
        sets = {"": np.asarray(req["served"], np.int64)}
        if ctl is not None:
            ctl_forced = None
            if forced is not None:
                ctl_forced = dict(forced, experts=np.full_like(
                    forced["experts"], -1))
            clg, _, cchosen = ctl.forward(tokens, ctl_forced, rows=rows)
            sets["ctl_"] = clg.argmax(axis=-1)
            if forced is not None:
                got = np.stack(cchosen, axis=1)[forced["pos"]]
                g = _route_gap(rls, got, forced["pos"], k)
                out["ctl_route_gap"] = max(out["ctl_route_gap"] or 0.0, g)
                b, n = _below_rule(ref, rls, got, np.full_like(got, LO),
                                   forced["pos"])
                below["ctl_"][0] += b
                below["ctl_"][1] += n
        for tag, toks in sets.items():
            gaps = lg.max(axis=-1) - lg[np.arange(len(toks)), toks]
            gaps[0] = min(float(f.max() - f[toks[0]]) for f in first)
            out[tag + "token_gap"] = max(out[tag + "token_gap"],
                                         float(gaps.max()))
        if forced is not None:
            g = _route_gap(rls, forced["experts"], forced["pos"], k)
            out["route_gap"] = max(out["route_gap"] or 0.0, g)
            b, n = _below_rule(ref, rls, forced["experts"], forced["prec"],
                               forced["pos"])
            below[""][0] += b
            below[""][1] += n
        out["tokens"] += len(req["served"])
        out["requests"] += 1
    for tag, (b, n) in below.items():
        if n and tag + "lo_excess" in out:
            out[tag + "lo_excess"] = 100.0 * b / n
    return out


def sample_requests(done: List[Dict], n: int, seed: int) -> List[Dict]:
    """Up to ``n`` requests drawn from the seed, the longest always in."""
    done = [r for r in done if len(r["served"])]
    if len(done) <= n:
        return done
    size = [len(r["prompt"]) + len(r["served"]) for r in done]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(done)) if i != longest]
    pick = np.random.default_rng(seed).choice(rest, n - 1, replace=False)
    return [done[longest]] + [done[i] for i in sorted(pick)]


def verdict(numbers: Dict, limits: Dict[str, float],
            failed: int) -> (bool, List[Dict]):
    """(correct, [{"name", "value", "limit"}]) for every number with a
    reading; a number without a limit fails."""
    rows = []
    ok = failed == 0 and numbers.get("requests", 0) > 0
    for name in COMPARED:
        v = numbers.get(name)
        if v is None:
            continue
        lim: Optional[float] = limits.get(name)
        rows.append({"name": name, "value": v, "limit": lim})
        ok = ok and lim is not None and v <= lim
    return ok, rows
