"""Traffic generator: one general reader of the mixes in ``bench/traffic/``.

A mix is a JSON file of parameters; nothing here knows a mix by name.  Every
mix is a closed loop: each client sends its next request, with no think
time, when the server releases its previous one.

    clients         number of clients
    requests_per_client
    max_batch, admit_k    scheduler slots and concurrent admissions
    prompt, output  length distributions: {"dist": "lognormal", "median",
                    "sigma", "min", "max"} | {"dist": "uniform", "min",
                    "max"} | {"dist": "fixed", "value"}
    warmup          {"prefill_calls", "decode_steps", "settle"}: when the
                    window opens (see ``cell.Run.warm``)
    check           {"max_requests"}: how many served requests the output
                    check compares at most

Every seed gets the same set of prompt and output lengths, drawn at fixed
quantiles of the distributions, dealt out in an order the seed shuffles, so
runs with different seeds do the same amount of work; token ids come from
the seed.  The length quantiles follow ``serving/workload.py``'s
``generate_workload``.
"""

from __future__ import annotations

import json
import pathlib
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
KEYS = {"clients", "requests_per_client", "max_batch", "admit_k", "prompt",
        "output", "warmup", "check"}


def load_mix(name: str) -> Dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"traffic {name}: the generator reads no {sorted(unknown)}")
    return mix


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of ``spec``'s distribution."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if kind == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(np.int64)
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.exp(np.log(float(spec["median"])) + float(spec["sigma"]) * z)
        return np.clip(np.rint(v), int(spec["min"]), int(spec["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def generate(mix: Dict, vocab: int, seed: int) -> List[List[Dict]]:
    """Per client, its requests in sending order: {"client", "index",
    "prompt" (int32), "max_new"}."""
    rng = np.random.default_rng(seed)
    clients = int(mix["clients"])
    n = clients * int(mix["requests_per_client"])
    plens = rng.permutation(quantile_lengths(mix["prompt"], n))
    olens = rng.permutation(quantile_lengths(mix["output"], n))
    out: List[List[Dict]] = [[] for _ in range(clients)]
    for i in range(n):
        c = i % clients
        out[c].append({"client": c, "index": len(out[c]),
                       "prompt": rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                       "max_new": int(olens[i])})
    return out


def max_context(mix: Dict) -> int:
    """Tokens a request of this mix can hold at most: prompt + output."""
    def top(spec):
        return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])
    return top(mix["prompt"]) + top(mix["output"])
