"""One run of one benchmark cell: build the served system through its normal
entry points, drive it with the cell's traffic for a fixed window, read the
metrics, and check what it served against the reference.

The system under test is ``BatchingServer.run()`` over the ``HobbitBackend``
that ``make_backend`` builds.  The harness only wraps that backend in a
protocol proxy, which

1. timestamps every first token (``join_step``) and every decode token
   (``step``, whose logits come back to the host);
2. sends each closed-loop client's next request when the server
   ``release``s the client's last one;
3. ends the run at the first call after ``seconds`` have passed, by
   raising out of ``run()``: the window is then every call from its opening
   to the end of the last call that began inside it, so it holds whole
   calls only and lasts ``seconds`` plus part of a call; requests still in
   flight are abandoned, not failed;
4. wraps every call in a ``jax.profiler.TraceAnnotation`` (``bench.<call>``)
   so idle gaps in a trace can be named by what the host was doing;

and records, per decode step and live row, the experts each MoE layer
routed to and the precision each was served in: the program's own rule
(``precision_decisions`` under ``engine.loader.th``) applied to the gates
the engine appends to ``engine.trace``, with hi turned lo where the staging
engine's ``serves_lo_downgrade`` said so (a wrapper reads its answers and
changes nothing).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import time
from typing import Dict, List, Optional

import jax
import numpy as np

import check
import counts
import traffic
import weights
import xplane
from reference import HI, LO, SKIP, Reference

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"


class WindowClosed(Exception):
    """Raised into ``BatchingServer.run()`` at the first call after the
    measured window closed."""


def load_json(path: pathlib.Path) -> Dict:
    return json.loads(path.read_text())


def load_config(name: str) -> Dict:
    return load_json(HERE / "configs" / f"{name}.json")


def program_config(cfgfile: Dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with the file's values, every one of them checked."""
    from repro.configs import get_config
    m = cfgfile["model"]
    base = get_config(cfgfile["arch"])
    moe = dataclasses.replace(base.moe, **m["moe"])
    cfg = dataclasses.replace(
        base, moe=moe, **{k: v for k, v in m.items() if k != "moe"}).validate()
    for k, v in m.items():
        got = dataclasses.asdict(cfg.moe) if k == "moe" else getattr(cfg, k)
        want = dict(got, **v) if k == "moe" else v
        if got != want:
            raise ValueError(f"config {cfgfile['name']}: {k} runs as {got!r}, "
                             f"the file says {v!r}")
    return cfg


def make_server(cfgfile: Dict, mix: Dict, model, params, proxy_cls, run):
    from repro.core import EngineConfig, Thresholds
    from repro.serving.api import BackendConfig, make_backend
    from repro.serving.batching import BatchingServer
    e = cfgfile["engine"]
    backend = make_backend(BackendConfig(
        kind="hobbit", paged=True, page_size=int(e["page_size"]),
        prefill_chunk=int(e["prefill_chunk"]),
        engine=EngineConfig(hi_slots=int(e["hi_slots"]),
                            lo_slots=int(e["lo_slots"]),
                            lo_bits=int(e["lo_bits"]),
                            group_size=int(e["group_size"]),
                            thresholds=Thresholds(*e["thresholds"]))),
        model, params)
    proxy = proxy_cls(backend, run)
    srv = BatchingServer(proxy, max_batch=int(mix["max_batch"]),
                         max_len=traffic.max_context(mix) + 1,
                         admit_k=int(mix["admit_k"]), policy="fifo",
                         temperature=0.0)
    return srv, proxy


@dataclasses.dataclass
class Req:
    """What the harness knows of one request."""
    rid: int
    client: int
    prompt: np.ndarray
    max_new: int
    request: object = None          # the server's Request
    sent: float = 0.0
    begun: Optional[float] = None   # join_begin
    first: Optional[float] = None   # first token
    times: List[float] = dataclasses.field(default_factory=list)
    served: List[int] = dataclasses.field(default_factory=list)
    records: List[Dict] = dataclasses.field(default_factory=list)
    fed: int = 0                    # prompt tokens prefilled so far
    nonfinite: bool = False


class Run:
    """The state of one run: traffic, window, per-call and per-request
    records."""

    def __init__(self, mix: Dict, requests: List[List[Dict]], seconds: float,
                 trace_dir: Optional[pathlib.Path], chunk: int):
        self.mix, self.seconds, self.trace_dir, self.chunk = mix, seconds, trace_dir, chunk
        self.pending = [list(c) for c in requests]
        self.reqs: Dict[int, Req] = {}
        self.by_prompt: Dict[int, int] = {}
        self.slot_rid: Dict[int, int] = {}
        self.phase = "warmup"
        self.t_open = self.t_close = self.t_end = None
        self.calls: List[Dict] = []
        self.prefill_calls = self.decode_steps = 0
        self.stats_open = self.stats_close = None
        self.server = None
        self.compiles: Dict[str, int] = {}
        self._win = None

    # ---- traffic ----
    def send(self, client: int) -> None:
        if not self.pending[client]:
            return
        from repro.serving.batching import Request
        spec = self.pending[client].pop(0)
        rid = len(self.reqs)
        r = Req(rid=rid, client=client, prompt=spec["prompt"],
                max_new=spec["max_new"])
        r.request = Request(rid=rid, prompt=r.prompt, max_new_tokens=r.max_new)
        r.sent = time.perf_counter()
        self.reqs[rid] = r
        self.by_prompt[id(r.prompt)] = rid
        self.server.submit(r.request)

    def start(self, server) -> None:
        self.server = server
        for c in range(len(self.pending)):
            self.send(c)

    # ---- window ----
    def warm(self) -> bool:
        """Warm-up is over after the mix's counts of prefill calls and
        decode steps and, where it asks to "settle", once no request is
        still being admitted."""
        w = self.mix.get("warmup", {})
        settled = not w.get("settle") or all(
            r.first is not None for r in self.reqs.values())
        return (self.prefill_calls >= int(w.get("prefill_calls", 0))
                and self.decode_steps >= int(w.get("decode_steps", 0))
                and settled)

    def before_call(self, backend, tokens=None) -> None:
        now = time.perf_counter()
        if self.phase == "window" and now >= self.t_close:
            if tokens is not None:
                self.note_fed(backend, tokens)
            self.close_window()
            raise WindowClosed()
        if self.phase == "warmup" and self.warm():
            if self.trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(self.trace_dir),
                                         profiler_options=opts)
                self._win = jax.profiler.TraceAnnotation("bench.window")
                self._win.__enter__()
            self.stats_open = self.stats_close = backend.stats()
            self.t_open = time.perf_counter()
            self.t_close = self.t_open + self.seconds
            self.phase = "window"

    def close_window(self) -> None:
        ends = [c["t1"] for c in self.calls if c["in_win"]]
        self.t_end = max(ends) if ends else time.perf_counter()
        self.phase = "closed"
        if self._win is not None:
            self._win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._win = None

    def in_window(self, t: float) -> bool:
        return self.phase == "window" and self.t_open <= t

    def on_event(self, name: str, *_a, **_k) -> None:
        """Counts JAX's compile and compile-cache events in the window:
        there should be none."""
        if self.phase == "window" and ("compil" in name or "cache" in name):
            self.compiles[name] = self.compiles.get(name, 0) + 1

    # ---- records ----
    def note_fed(self, backend, tokens) -> None:
        """Tokens fed to a decode step are the tokens the server produced
        last for each live slot."""
        for slot in np.flatnonzero(backend.engine.active):
            rid = self.slot_rid.get(int(slot))
            if rid is not None:
                self.reqs[rid].served.append(int(tokens[slot]))


class Proxy:
    """Protocol proxy over a HobbitBackend (see the module docstring)."""

    def __init__(self, backend, run: Run):
        self._b = backend
        self.model = backend.model
        self.engine = backend.engine
        self.run = run
        self._dg = set()
        sched = self.engine.scheduler
        orig = sched.serves_lo_downgrade

        def serves_lo_downgrade(layer, expert):
            got = orig(layer, expert)
            if got:
                self._dg.add((int(layer), int(expert)))
            return got

        sched.serves_lo_downgrade = serves_lo_downgrade

    def __getattr__(self, name):
        return getattr(self._b, name)

    def _call(self, name, fn, *args, **kw):
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            return fn(*args, **kw)

    def start_batch(self, batch, max_len):
        return self._call("start_batch", self._b.start_batch, batch, max_len)

    def can_admit(self, tokens, *, prompt=None):
        return self._call("can_admit", self._b.can_admit, tokens, prompt=prompt)

    def stats(self):
        return self._call("stats", self._b.stats)

    def pause(self, slot):
        return self._call("pause", self._b.pause, slot)

    def resume(self, slot, snapshot):
        return self._call("resume", self._b.resume, slot, snapshot)

    def join_begin(self, slot, prompt, reserve_tokens=None):
        rid = self.run.by_prompt.get(id(prompt))
        if rid is not None:
            self.run.slot_rid[int(slot)] = rid
            self.run.reqs[rid].begun = time.perf_counter()
        return self._call("join_begin", self._b.join_begin, slot, prompt,
                          reserve_tokens=reserve_tokens)

    def join_step(self):
        run = self.run
        run.before_call(self)
        admitting = {s: r for s, r in run.slot_rid.items()
                     if not run.reqs[r].times}
        t0 = time.perf_counter()
        done = self._call("join_step", self._b.join_step)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.record"):
            chunks = []
            for s, rid in admitting.items():
                r = run.reqs[rid]
                n = min(run.chunk, len(r.prompt) - r.fed)
                chunks.append((len(r.prompt), r.fed, r.fed + n))
                r.fed += n
            tokens = 0
            for s, lg in done.items():
                r = run.reqs[run.slot_rid[int(s)]]
                r.first = t1
                r.times.append(t1)
                r.nonfinite |= not bool(np.isfinite(lg).all())
                tokens += 1
            run.prefill_calls += 1
            self._log("join_step", t0, t1, tokens=tokens, chunks=chunks)
        return done

    def step(self, tokens):
        run, eng = self.run, self.engine
        run.before_call(self, tokens)
        live = np.flatnonzero(eng.active)
        run.note_fed(self, tokens)
        n_trace = len(eng.trace)
        self._dg = set()
        t0 = time.perf_counter()
        lg = self._call("step", self._b.step, tokens)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.record"):
            self._record_step(live, eng.trace[n_trace:], lg, t0, t1)
        return lg

    def release(self, slot):
        out = self._call("release", self._b.release, slot)
        run = self.run
        rid = run.slot_rid.pop(int(slot), None)
        if rid is not None:
            r = run.reqs[rid]
            if r.request.output is not None:
                r.served = [int(t) for t in r.request.output]
            if run.phase != "closed":
                run.send(r.client)
        return out

    # ---- per-step record ----
    def _record_step(self, live, entries, lg, t0, t1):
        from repro.core.scoring import precision_decisions
        run, eng = self.run, self.engine
        assert len(entries) == len(live), (len(entries), len(live))
        th = eng.loader.th
        k = eng.cfg.moe.top_k
        n_layers = len(entries[0]) if entries else 0
        lo_pairs = np.zeros(n_layers, np.int64)
        count = {HI: 0, LO: 0, SKIP: 0}
        lengths = []
        for slot, entry in zip(live, entries):
            rid = run.slot_rid.get(int(slot))
            experts = np.zeros((n_layers, k), np.int32)
            prec = np.zeros((n_layers, k), np.int32)
            for mi, tl in enumerate(entry):
                dec = np.asarray(precision_decisions(np.asarray(tl.gate_vals),
                                                     th), np.int32)
                for j, e in enumerate(tl.experts):
                    if dec[j] == HI and (mi, int(e)) in self._dg:
                        dec[j] = LO
                experts[mi] = tl.experts
                prec[mi] = dec
                lo_pairs[mi] += int((dec == LO).sum())
            for v in prec.ravel():
                count[int(v)] += 1
            if rid is None:
                continue
            r = run.reqs[rid]
            r.records.append({"experts": experts, "prec": prec})
            r.times.append(t1)
            r.nonfinite |= not bool(np.isfinite(lg[slot]).all())
            lengths.append(len(r.prompt) + len(r.served))
        run.decode_steps += 1
        self._log("step", t0, t1, tokens=len(live), lengths=lengths,
                  lo_pairs=lo_pairs.tolist(), rows=len(live), prec=count)

    def _log(self, kind, t0, t1, **kw):
        run = self.run
        rec = dict(kind=kind, t0=t0, t1=t1, in_win=run.in_window(t1), **kw)
        run.calls.append(rec)
        if rec["in_win"]:
            run.stats_close = self._b.stats()


def warm_prefill(backend, mix: Dict, chunk: int, vocab: int) -> None:
    """Compile the chunked prefill at every row count the cell's admissions
    can have (1 .. admit_k), through the backend's own protocol."""
    b = int(mix["max_batch"])
    backend.start_batch(b, traffic.max_context(mix) + 1)
    for slot in range(b):
        backend.release(slot)
    prompt = (np.arange(chunk) % vocab).astype(np.int32)
    for rows in range(1, int(mix["admit_k"]) + 1):
        for slot in range(rows):
            backend.join_begin(slot, prompt, reserve_tokens=chunk + 1)
        backend.join_step()
        for slot in range(rows):
            backend.release(slot)


def check_weights(model, params) -> None:
    """The benchmark's weight tree has the layout ``model.init`` makes."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ws = jax.tree_util.tree_structure(want)
    gs = jax.tree_util.tree_structure(params)
    if ws != gs:
        raise ValueError(f"weight tree {gs} is not the model's {ws}")
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(params)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"weight leaf {g.shape} {g.dtype} is not the "
                             f"model's {w.shape} {w.dtype}")


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Ctx:
    """What a metric reader reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_calls(self, kind: str) -> List[Dict]:
        return [c for c in self.calls if c["kind"] == kind and c["in_win"]]

    def delta(self, *path) -> float:
        a, b = self.stats_open, self.stats_close
        for p in path:
            a, b = a[p], b[p]
        return float(b) - float(a)


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The cell's metrics: end-to-end without a trace, per-layer with one."""
    table = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in table if cell in m.get("workloads", [cell])]


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Dict, control: bool = False,
             cfgfile: Dict = None, mix: Dict = None, limits: Dict = None,
             log=print) -> Dict:
    """One run of ``cell`` (an entry of ``bench``'s workloads); returns the
    result line's fields.  ``cfgfile``, ``mix`` and ``limits`` replace the
    cell's own (tests run a cell at a tiny size)."""
    from repro.models import build_model
    cell_name = cell["name"]
    cfgfile = cfgfile or load_config(cell["config"])
    mix = mix or traffic.load_mix(cell["traffic"])
    limits = check.load_limits(cell_name) if limits is None else limits
    m, eng = cfgfile["model"], cfgfile["engine"]
    from repro.core.scoring import PREC_HI, PREC_LO, PREC_SKIP
    if (PREC_HI, PREC_LO, PREC_SKIP) != (HI, LO, SKIP):
        raise RuntimeError("the program's precision codes are not the "
                           "reference's")
    peak = load_json(HERE / "peaks.json").get(jax.devices()[0].device_kind)

    model = build_model(program_config(cfgfile))
    params = weights.make(m, seed)
    check_weights(model, params)
    jax.block_until_ready(params)
    log(f"weights made at {time.perf_counter() - t_start:.3f} s")
    requests = traffic.generate(mix, int(m["vocab_size"]), seed)
    trace_dir = None
    if trace:
        trace_dir = OUT / f"trace-{cell_name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(mix, requests, seconds, trace_dir, int(eng["prefill_chunk"]))
    srv, proxy = make_server(cfgfile, mix, model, params, Proxy, run)
    del params
    log(f"backend built at {time.perf_counter() - t_start:.3f} s")
    warm_prefill(proxy._b, mix, int(eng["prefill_chunk"]), int(m["vocab_size"]))
    jax.monitoring.register_event_duration_secs_listener(run.on_event)
    log(f"prefill warmed at {time.perf_counter() - t_start:.3f} s")

    run.start(srv)
    try:
        srv.run()
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the traffic ran out before the window closed")
    devs = jax.devices()
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devs[:int(cell["chips"])])
    srv.close()
    run.server = None
    del srv, proxy
    gc.collect()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"window opened at {run.t_open - t_start:.3f} s; {live} bytes "
        "still on the device after it closed")

    tr = None
    if trace:
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        tr = xplane.Trace.load(str(files[-1]), seconds=run.t_end - run.t_open)
        shutil.rmtree(trace_dir, ignore_errors=True)

    reqs = list(run.reqs.values())
    ctx = Ctx(m=m, eng=eng, mix=mix, peak=peak, seconds=run.t_end - run.t_open,
              calls=run.calls, reqs=reqs, stats_open=run.stats_open,
              stats_close=run.stats_close, trace=tr, counts=counts,
              t_open=run.t_open, t_close=run.t_end,
              setup_s=run.t_open - t_start)
    metrics = {}
    for spec in metrics_for(bench, cell_name, trace):
        v = load_reader(spec["name"])(ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    attempted = len(reqs)
    failed = sum(r.nonfinite for r in reqs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak_bytes}
    out = {"metrics": metrics, "attempted": attempted, "failed": failed,
           "device": device, "compiles_in_window": run.compiles,
           "counts": {"decode_steps": len(ctx.window_calls("step")),
                      "prefill_calls": len(ctx.window_calls("join_step")),
                      "lo_share": load_reader("lo_share")(ctx)}}
    if tr is not None:
        out["device"]["busy_s"] = tr.busy_s()
        lo, hi = tr.window()
        out["device"]["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}

    # ---- correctness, after the program's state is gone ----
    t_ref = time.perf_counter()
    done = [{"prompt": r.prompt, "served": r.served, "records": r.records}
            for r in reqs]
    sample = check.sample_requests(done, int(mix["check"]["max_requests"]),
                                   seed)
    params = weights.make(m, seed)
    numbers = check.compare(Reference(params, m, eng), sample,
                            ctl=Reference(params, m, eng, fp8=True)
                            if control else None)
    ok, rows = check.verdict(numbers, limits, failed)
    out["correct"] = bool(ok)
    out["compared"] = rows
    out["check"] = {"requests": numbers["requests"], "tokens": numbers["tokens"],
                    "lo_excess": numbers["lo_excess"],
                    "seconds": time.perf_counter() - t_ref}
    if control:
        ctl = {k[4:]: v for k, v in numbers.items() if k.startswith("ctl_")}
        ctl_ok, ctl_rows = check.verdict(dict(ctl, requests=numbers["requests"]),
                                         limits, 0)
        out["control"] = dict(ctl, correct=bool(ctl_ok))
        log(f"control: correct {ctl_ok}, " + ", ".join(
            f"{r['name']} {r['value']!r} limit {r['limit']!r}" for r in ctl_rows))
    return out
