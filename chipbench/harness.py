"""One run of one cell: set-up, a closed-loop window driven through
``InferenceServer.begin/submit/step``, the check against the plain
reference, and one result line.

Everything the run times is taken here, on the host clock, around the
program's public calls: when a client sent each request, when each
``step()`` returned the tokens it produced.  Per-layer metrics come from
a profiler trace of a separate ``--trace 1`` run, reduced by
``trace_reduce.py`` and read by the files in ``layer_metrics/``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time

import jax
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

import model as model_mod          # noqa: E402
import oracle                      # noqa: E402
import stats                       # noqa: E402
import trace_reduce as trace_mod   # noqa: E402
import traffic as traffic_mod      # noqa: E402
import work                        # noqa: E402

TRACE_SECONDS = 10.0      # longest traced window: traces are large
DRAIN_STEPS = 64          # most steps after the close to deliver the
                          # first token of every request sent in it
SPAN = "chipbench.step"


class CompileLog:
    """Traces, backend compiles and persistent-cache hits and misses,
    from JAX's own monitoring events."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"traces": self.traces, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses,
                "compile_s": round(self.compile_s, 3)}

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - snap[k], 3) for k in now}


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration, mix and metrics from
    ``BENCHMARK.json``, each found by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    conf = model_mod.load_config(cell["config"])
    return {"cell": cell, "conf": conf, **config_modules(conf),
            "mix": traffic_mod.load_mix(cell["traffic"]),
            "limits": json.loads(
                (HERE / "limits" / f"{workload}.json").read_text()),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_modules(conf: dict) -> dict:
    """The configuration's plain reference and work counts: the modules
    ``<name>.py`` of this directory that its keys ``"reference"`` and
    ``"work"`` name, ``reference.py`` and ``work.py`` where a key is
    absent."""
    out = {}
    for key in ("reference", "work"):
        name = conf.get(key, key)
        path = HERE / f"{name}.py"
        if not name.isidentifier() or not path.is_file():
            raise ValueError(f"configuration {conf['name']!r}: {key} "
                             f"{name!r} is no module of {HERE}")
        out[key] = _load(path, f"config_{key}_{name}")
    return out


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise RuntimeError(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak() -> int:
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


@dataclasses.dataclass
class Rec:
    uid: int
    prompt: np.ndarray
    max_tokens: int
    t_send: float
    t_first: float | None = None
    t_last: float | None = None
    n: int = 0
    tokens: np.ndarray | None = None
    t_done: float | None = None
    failed: bool = False
    walk: bool = False            # a warm-up request, not a client's


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    prefill: list          # real prompt lengths admitted this step
    decode_ctx: list       # attended context of each decoded row
    tokens: int            # tokens this step returned


class Loop:
    """A closed loop of clients around one open server session."""

    def __init__(self, server, gen, clients: int, traced: bool = False):
        from repro.serve.sampling import SamplingParams
        from repro.serve.scheduler import Request
        self._Request, self._SP = Request, SamplingParams
        self.server, self.gen, self.clients = server, gen, clients
        self.traced = traced
        self.recs: dict = {}
        self.steps: list = []
        self.itl: list = []            # (t of the later token, gap s)
        self.sending = True
        self.live = 0                  # requests sent and not finished
        self._uid = 0

    def send(self, prompt=None, max_tokens=None, walk=False):
        if prompt is None:
            prompt, max_tokens = self.gen.next_request()
        uid = self._uid
        self._uid += 1
        req = self._Request(uid=uid, prompt=prompt,
                            sampling=self._SP(max_tokens=int(max_tokens)))
        rec = Rec(uid, prompt, int(max_tokens), time.perf_counter(),
                  walk=walk)
        self.recs[uid] = rec
        try:
            self.server.submit(req)
            if not walk:
                self.live += 1
        except ValueError:
            rec.failed = True
        return rec

    def fill(self):
        for _ in range(self.clients - self.live):
            self.send()

    def step(self) -> StepRec:
        t0 = time.perf_counter()
        if self.traced:
            with jax.profiler.TraceAnnotation(SPAN):
                res = self.server.step()
        else:
            res = self.server.step()
        t1 = time.perf_counter()
        admitted = set(res.admitted)
        prefill, ctx, total = [], [], 0
        for uid, n in res.produced.items():
            rec = self.recs[uid]
            gained = n - rec.n
            total += gained
            if uid in admitted:
                prefill.append(len(rec.prompt))
            if rec.n == 0:
                rec.t_first = t1
                if gained > 1:        # prefill and first decode together
                    self.itl.append((t1, 0.0))
            else:
                self.itl.append((t1, t1 - rec.t_last))
            decoded = gained - (1 if uid in admitted else 0)
            for k in range(decoded):
                ctx.append(len(rec.prompt) + n - decoded + k)
            rec.t_last, rec.n = t1, n
        if res.nan:
            for uid in res.produced:
                self.recs[uid].failed = True
        for uid in res.finished:
            rec = self.recs[uid]
            rec.tokens = self.server.result(uid)
            rec.t_done = t1
            if not rec.walk:
                self.live -= 1
        st = StepRec(t0, t1, prefill, ctx, total)
        self.steps.append(st)
        if self.sending:
            self.fill()
        return st

    def run_for(self, seconds: float):
        t_end = time.perf_counter() + seconds
        n0 = len(self.steps)
        while time.perf_counter() < t_end:
            self.step()
        return self.steps[n0:]

    def run_steps(self, n: int):
        for _ in range(n):
            self.step()


def warm(server, mix: dict, gen, log) -> tuple:
    """Compile every program the window can need, through the public
    session API: the walks of ``traffic.warm_walks`` (every prefill shape
    of the mix and every decode width its requests can make the server
    pick).  Walks from the longest prompt are left for the ramp, where
    they run beside the clients' requests at no cost; the others run here
    one at a time.  Returns the set-up record and the ramp's walks."""
    snap = log.snapshot()
    t0 = time.perf_counter()
    walks = traffic_mod.warm_walks(mix)
    top = max(mix["prompt_lengths"])
    n_steps = 0
    for prompt_len, n_dec in walks:
        if prompt_len == top:
            continue
        server.begin()
        loop = Loop(server, gen, clients=0)
        loop.sending = False
        if loop.send(gen.prompt(prompt_len), n_dec + 1).failed:
            raise RuntimeError(f"the server refused a warm-up request of "
                               f"{prompt_len} + {n_dec + 1} tokens")
        while server.has_work:
            loop.step()
            n_steps += 1
        server.end()
    return ({"warm_s": round(time.perf_counter() - t0, 3),
             "warm_steps": n_steps, **log.since(snap)},
            [w for w in walks if w[0] == top])


def ramp(server, mix: dict, gen, walks: list) -> tuple:
    """Open the session and run the closed loop for one longest request's
    life (or the longest ramp walk's), with the walks submitted first so
    that each is the batch's highest position at every step."""
    server.begin()
    loop = Loop(server, gen, mix["clients"])
    for prompt_len, n_dec in walks:
        loop.send(gen.prompt(prompt_len), n_dec + 1, walk=True)
    loop.fill()
    n = max([mix["output_max"]] + [n_dec + 1 for _, n_dec in walks])
    t = time.perf_counter()
    loop.run_steps(n)
    if any(r.t_done is None for r in loop.recs.values() if r.walk):
        raise RuntimeError("a warm-up walk did not finish in the ramp")
    return loop, time.perf_counter() - t, n


def layer_readers(names) -> dict:
    """``layer_metrics/<name>.py`` for each per-layer metric, by name."""
    return {name: _load(HERE / "layer_metrics" / f"{name}.py",
                        f"layer_metric_{i}")
            for i, name in enumerate(names)}


def run(spec: dict, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True, control: bool = False,
        log=print) -> dict:
    """One run of the cell in ``spec`` (see :func:`load_cell`).  Returns
    the result object; earlier lines go to ``log``.  ``control`` also
    reads the control's gap (``control.py``; never in a benchmark run)."""
    from repro.serve.engine import InferenceServer

    cell, conf, mix = spec["cell"], spec["conf"], spec["mix"]
    device = device_info(cell["chips"], require_tpu)
    clog = CompileLog()
    ref = spec["reference"]
    cfg = model_mod.arch(conf)
    bits = (model_mod.plan_bits(cfg, conf["plan"], ref) if conf["plan"]
            else None)

    t = time.perf_counter()
    params = model_mod.make_params(cfg, seed)
    jax.block_until_ready(params)
    plan = model_mod.make_plan(conf, bits) if bits is not None else None
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    server = InferenceServer(cfg, params, plan, cache="paged",
                             page_size=mix["page_size"],
                             max_len=mix["max_len"],
                             max_batch=mix["max_batch"], pages=mix["pages"])
    jax.block_until_ready(server.params)
    del params                     # remade for the reference after the window
    t_bind = time.perf_counter() - t
    gen = traffic_mod.ClosedLoop(mix, seed, cfg.vocab)
    warm_info, ramp_walks = warm(server, mix, gen, clog)
    snap = clog.snapshot()
    loop, ramp_s, ramp_steps = ramp(server, mix, gen, ramp_walks)
    log(f"set-up: weights {t_weights:.2f} s, plan binding and server "
        f"{t_bind:.2f} s, warm-up {warm_info}, ramp {ramp_s:.2f} s "
        f"({ramp_steps} steps, {clog.since(snap)})")

    snap = clog.snapshot()
    tdir = None
    if traced:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        loop.traced = True
        jax.profiler.start_trace(
            tdir, profiler_options=trace_mod.profile_options())
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    window = loop.run_for(min(seconds, TRACE_SECONDS) if traced
                          else seconds)
    t_close = window[-1].t1
    if traced:
        jax.profiler.stop_trace()
    in_window = clog.since(snap)
    log(f"compiles in the window: {in_window}")

    # deliver the first token of every request sent in the window
    loop.sending = False
    sent = [r for r in loop.recs.values() if t_open <= r.t_send < t_close]
    for _ in range(DRAIN_STEPS):
        if all(r.t_first is not None or r.failed for r in sent):
            break
        loop.step()
    mem_peak = memory_peak()
    finished = [r for r in loop.recs.values()
                if r.t_done is not None and t_open <= r.t_done <= t_close
                and not r.failed and not r.walk]
    failed = sum(1 for r in sent if r.failed or r.t_first is None)
    server.end()
    preempted = server.stats["preemptions"]
    del server, loop.server
    gc.collect()

    window_s = t_close - t_open
    n_tokens = sum(s.tokens for s in window)
    ttft = [(r.t_first - r.t_send) * 1e3 for r in sent
            if r.t_first is not None]
    itl = [g * 1e3 for (tt, g) in loop.itl if t_open < tt <= t_close]
    log(f"window: {window_s:.3f} s, {len(window)} steps, {n_tokens} "
        f"tokens, {len(sent)} requests sent, {len(finished)} finished; "
        f"percentile samples: ttft {len(ttft)}, itl {len(itl)}; "
        f"preemptions {preempted}")

    metrics = {}
    extra = {}
    if traced:
        tr = trace_mod.reduce_dir(tdir, SPAN)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = trace_mod.Context(trace=tr, steps=window, cfg=cfg,
                                bits=bits, peaks=work.peaks(device["kind"]),
                                work=spec["work"])
        readers = layer_readers([m["name"] for m in spec["per_layer"]])
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
        for name, mod in readers.items():
            v = mod.read(ctx)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": units[name]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        extra["breakdown"] = tr.breakdown()
    else:
        e2e = {"output_tok_s": n_tokens / window_s,
               "ttft_p95_ms": stats.percentile(ttft, 95) if ttft else None,
               "itl_p95_ms": stats.percentile(itl, 95) if itl else None,
               "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = mem_peak

    # the check: served tokens against the plain reference
    t = time.perf_counter()
    params = model_mod.make_params(cfg, seed)
    sample = oracle.sample(finished, mix["check_requests"], seed)
    gap, ctl = (oracle.gaps(params, bits, sample, cfg, ref, control=control)
                if sample else (None, None))
    short = sum(1 for r in sample if len(r.tokens) != r.max_tokens)
    limit = spec["limits"]["logit_gap"]
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "short_streams": {"value": short, "limit": 0},
              "failed": {"value": failed, "limit": 0}}
    if control:
        checks["control_gap"] = {"value": ctl, "limit": limit}
    correct = (gap is not None and gap <= limit and short == 0
               and failed == 0)
    log(f"check: {len(sample)} requests, "
        f"{sum(len(r.tokens) for r in sample)} served tokens, "
        f"reference {time.perf_counter() - t:.2f} s")
    out = {"correct": correct, "attempted": len(sent), "failed": failed,
           "metrics": metrics, "device": device, **extra,
           "checks": checks}
    for name, c in checks.items():
        log(f"{name} {c['value']} limit {c['limit']}")
    return out
