"""Observability subsystem tests: the metrics registry (counters,
gauges, histograms, no-op-when-disabled, idempotent phase points), the
request tracer's lifecycle grammar, end-to-end server tracing across the
parity matrix (dense/paged x float/quantized x solo/batched/streaming/
preempted) with scheduler event-ordering properties, the exporter +
validator round-trip, and the serving loop's profiler spans and the
decode step's named scopes."""
import json
import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import registry as cfg_registry
from repro.models import lm
from repro.obs import validate as obs_validate
from repro.serve import engine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Request


@pytest.fixture(scope="module")
def llama():
    cfg = cfg_registry.get("llama3.2-1b-smoke")
    return cfg, lm.init_params(cfg, jax.random.key(0))


def _reqs(cfg, lens, sp, gap=0, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=s).astype(np.int32),
                    sampling=sp, arrival=gap * i)
            for i, s in enumerate(lens)]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_labels(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("x_total", "help", labels=("kind",))
        c.inc(kind="a")
        c.inc(2, kind="a")
        c.inc(kind="b")
        assert c.value(kind="a") == 3.0
        assert c.value(kind="b") == 1.0
        g = reg.gauge("y")
        g.set(7.5)
        g.set(2.5)
        assert g.value() == 2.5
        with pytest.raises(ValueError):
            c.inc(-1, kind="a")
        with pytest.raises(ValueError):
            c.inc(wrong="a")

    def test_get_or_create_and_mismatch(self):
        reg = obs.MetricsRegistry()
        c1 = reg.counter("n_total", labels=("k",))
        assert reg.counter("n_total", labels=("k",)) is c1
        with pytest.raises(ValueError):
            reg.gauge("n_total", labels=("k",))     # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("n_total", labels=("j",))   # label mismatch
        reg.histogram("h")
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1.0, 2.0))  # bucket mismatch

    def test_histogram_buckets_and_snapshot(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 5.0, 100.0):
            h.observe(v)
        assert h.count() == 5
        snap = reg.snapshot()["lat_seconds"]
        (series,) = snap["series"]
        # le is inclusive: 0.1 falls in the first bucket
        assert series["buckets"] == [[0.1, 2], [1.0, 3], [10.0, 4],
                                     ["+Inf", 5]]
        assert series["count"] == 5
        assert series["sum"] == pytest.approx(105.65)

    def test_default_latency_buckets_log_spaced(self):
        b = obs.LATENCY_BUCKETS_S
        assert b[0] == pytest.approx(1e-6)
        assert b[-1] == pytest.approx(1e2)
        ratios = [b2 / b1 for b1, b2 in zip(b, b[1:])]
        assert all(r == pytest.approx(10 ** 0.25) for r in ratios)

    def test_disabled_registry_is_noop(self):
        reg = obs.MetricsRegistry(enabled=False)
        c = reg.counter("x_total")
        c.inc()
        reg.gauge("y").set(1.0)
        reg.histogram("h").observe(0.5)
        reg.emit_phase_point("p", 0, {"loss": 1.0})
        assert reg.snapshot() == {}
        # every disabled accessor returns the one shared no-op object
        assert c is reg.histogram("h")

    def test_emit_phase_point_idempotent(self):
        reg = obs.MetricsRegistry()
        reg.emit_phase_point("search", 0, {"task": 1.0, "reg": 2.0})
        reg.emit_phase_point("search", 1, {"task": 0.9, "reg": 1.9})
        # replayed steps (checkpoint resume) must not re-count
        reg.emit_phase_point("search", 0, {"task": 1.0, "reg": 2.0})
        reg.emit_phase_point("search", 1, {"task": 0.9, "reg": 1.9})
        reg.emit_phase_point("search", 2, {"task": 0.8, "reg": 1.8})
        pts = reg.counter("compress_step_points_total",
                          labels=("phase", "metric"))
        assert pts.value(phase="search", metric="task") == 3
        assert pts.value(phase="search", metric="reg") == 3
        val = reg.gauge("compress_step_value", labels=("phase", "metric"))
        assert val.value(phase="search", metric="task") == \
            pytest.approx(0.8)
        # an independent metric name at the same steps is unaffected
        reg.emit_phase_point("search", 1, {"acc_quant": 0.5})
        assert pts.value(phase="search", metric="acc_quant") == 1

    def test_prometheus_round_trip(self):
        reg = obs.MetricsRegistry()
        reg.counter("req_total", "requests served",
                    labels=("kind",)).inc(3, kind='we"ird\nname')
        reg.gauge("pages").set(4)
        reg.histogram("lat_seconds", buckets=(0.5, 5.0)).observe(1.0)
        text = obs.to_prometheus(reg)
        fams = obs_validate.parse_prometheus(text)
        assert fams["req_total"]["type"] == "counter"
        name, labels, value = fams["req_total"]["samples"][0]
        assert labels == {"kind": 'we"ird\nname'} and value == 3.0
        assert fams["lat_seconds"]["type"] == "histogram"
        # 2 buckets + +Inf + sum + count
        assert len(fams["lat_seconds"]["samples"]) == 5


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_manual_lifecycle_and_latencies(self):
        reg = obs.MetricsRegistry()
        tr = obs.RequestTracer(reg)
        tr.event(0, "enqueued", n=4)
        tr.event(0, "admitted", n=4, pages_held=2, slot=0, resumed=False)
        tr.event(0, "prefilled", n=4, pages_held=2, slot=0)
        tr.event(0, "first_token", n=1, pages_held=2, slot=0)
        tr.event(0, "decode", n=2, pages_held=3, slot=0)
        tr.event(0, "preempted", n=2, pages_held=0, slot=0)
        tr.event(0, "admitted", n=6, pages_held=3, slot=1, resumed=True)
        tr.event(0, "prefilled", n=6, pages_held=3, slot=1)
        tr.event(0, "decode", n=3, pages_held=3, slot=1)
        tr.event(0, "finished", n=3, pages_held=0, slot=1)
        assert tr.check_lifecycle(tr.lifecycle(0)) is None
        assert len(tr.ttfts()) == 1
        assert len(tr.token_latencies()) == 3   # first_token + 2 decodes
        assert tr.preemption_count() == 1
        assert tr.pages_held_hwm() == 3
        # registry saw one ttft and one latency observation per token
        assert reg.histogram("serve_ttft_seconds").count() == 1
        assert reg.histogram("serve_token_latency_seconds").count() == 3
        assert reg.counter("serve_tokens_total").value() == 3

    def test_invalid_lifecycles_rejected(self):
        check = obs.RequestTracer.check_lifecycle
        assert check([]) is not None
        assert check(["admitted"]) is not None
        assert check(["enqueued", "admitted", "first_token"]) is not None
        assert check(["enqueued", "admitted", "prefilled",
                      "first_token"]) is not None      # no finished
        assert check(["enqueued", "admitted", "prefilled", "decode",
                      "finished"]) is not None         # missing 1st token
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "finished", "decode"]) is not None
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "preempted", "admitted", "prefilled", "first_token",
                      "finished"]) is not None   # resume re-emits 1st tok
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "finished"]) is None
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "preempted", "admitted", "prefilled", "decode",
                      "finished"]) is None

    def test_episode_grammar_terminals(self):
        """timeout/cancelled terminals: may strike a queued, resident or
        preempted request, and a struck uid may be re-enqueued (the
        fleet's retry path) as a fresh episode; finished stays final."""
        check = obs.RequestTracer.check_lifecycle
        assert check(["enqueued", "cancelled"]) is None
        assert check(["enqueued", "timeout"]) is None
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "decode", "timeout"]) is None      # mid-decode
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "preempted", "cancelled"]) is None  # while evicted
        # retry episodes: timeout in queue, then a clean second episode
        assert check(["enqueued", "timeout",
                      "enqueued", "admitted", "prefilled", "first_token",
                      "decode", "finished"]) is None
        assert check(["enqueued", "cancelled", "enqueued",
                      "timeout"]) is None
        # finished must be the uid's last event overall
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "finished", "enqueued", "cancelled"]) is not None
        # finished requires a residency; terminals don't chain
        assert check(["enqueued", "finished"]) is not None
        assert check(["enqueued", "admitted", "prefilled", "first_token",
                      "preempted", "finished"]) is not None
        assert check(["enqueued", "timeout", "admitted", "prefilled",
                      "first_token", "finished"]) is not None  # no re-enq
        assert check(["enqueued", "timeout", "cancelled"]) is not None

    def test_queue_depth_gauge_and_wait_histogram(self):
        reg = obs.MetricsRegistry()
        tr = obs.RequestTracer(reg, replica="r0")
        g = reg.gauge("serve_queue_depth", labels=("replica",))
        h = reg.histogram("serve_queue_wait_seconds",
                          labels=("replica",))
        tr.event(0, "enqueued", n=4)
        tr.event(1, "enqueued", n=4)
        assert g.value(replica="r0") == 2.0
        tr.event(0, "admitted", n=4, slot=0)
        assert g.value(replica="r0") == 1.0
        assert h.count(replica="r0") == 1     # enqueued -> admitted
        tr.event(1, "cancelled", n=0)         # cancellation leaves queue
        assert g.value(replica="r0") == 0.0
        # a preemption re-enters the queue; its wait is measured from
        # the preemption, not the original enqueue
        tr.event(0, "prefilled", n=4, slot=0)
        tr.event(0, "first_token", n=1, slot=0)
        tr.event(0, "preempted", n=1, slot=0)
        assert g.value(replica="r0") == 1.0
        tr.event(0, "admitted", n=5, slot=0)
        assert g.value(replica="r0") == 0.0
        assert h.count(replica="r0") == 2
        assert len(tr.queue_waits()) == 2
        assert all(w >= 0.0 for w in tr.queue_waits())

    def test_solo_servers_use_empty_replica_label(self):
        reg = obs.MetricsRegistry()
        tr = obs.RequestTracer(reg)
        tr.event(0, "enqueued", n=1)
        assert reg.gauge("serve_queue_depth",
                         labels=("replica",)).value(replica="") == 1.0

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError):
            obs.RequestTracer().event(0, "teleported")

    def test_start_resets_trace_not_metrics(self):
        reg = obs.MetricsRegistry()
        tr = obs.RequestTracer(reg)
        tr.event(0, "enqueued", n=1)
        tr.start()
        assert tr.events == []
        assert reg.counter("serve_trace_events_total",
                           labels=("kind",)).value(kind="enqueued") == 1


# ---------------------------------------------------------------------------
# server tracing: lifecycle properties across the parity matrix
# ---------------------------------------------------------------------------

def _check_trace_properties(server, requests, out):
    """The satellite's scheduler event-ordering properties, asserted on
    one traced serve run."""
    tr = server.obs.tracer
    reg = server.obs.registry
    uids = {r.uid for r in requests}
    assert set(tr.uids()) == uids

    for uid in uids:
        evs = tr.events_for(uid)
        kinds = [e.kind for e in evs]
        err = obs.RequestTracer.check_lifecycle(kinds)
        assert err is None, f"uid {uid}: {kinds}: {err}"
        # admitted strictly before the first token
        assert kinds.index("admitted") < kinds.index("first_token")
        # pages return to 0 at finish; final n is the emitted stream
        last = evs[-1]
        assert last.kind == "finished" and last.pages_held == 0
        assert last.n == len(out[uid])

    # preempted requests are re-admitted in FRONT order: replay the
    # trace against a model deque -- a preemption pushes the uid to the
    # front, and the next resumed admission must pop exactly the head
    # (no fresh admission may overtake a waiting preempted request)
    front = []
    for ev in tr.events:
        if ev.kind == "preempted":
            front.insert(0, ev.uid)
        elif ev.kind == "admitted":
            if ev.extra.get("resumed"):
                assert front and front[0] == ev.uid, \
                    f"resumed {ev.uid} admitted out of FRONT order {front}"
                front.pop(0)
            else:
                assert ev.uid not in front
                assert not front, \
                    f"fresh {ev.uid} admitted while {front} waits in front"

    # histogram counts reconcile with the engine's token totals
    generated = server.stats["generated"]
    assert len(tr.token_latencies()) == generated
    assert reg.histogram("serve_token_latency_seconds").count() == \
        generated
    assert reg.counter("serve_tokens_total").value() == generated
    assert reg.histogram("serve_ttft_seconds").count() == len(uids)
    assert tr.preemption_count() == server.stats["preemptions"]


class TestServerTracing:
    @pytest.mark.parametrize("cache,plan_on", [
        ("dense", False), ("paged", False),
        ("dense", True), ("paged", True)])
    def test_lifecycle_matrix(self, llama, cache, plan_on):
        cfg, params = llama
        plan = engine.synthetic_plan(cfg, params, bits=None, seed=0) \
            if plan_on else None
        kwargs = {} if cache == "dense" else {
            "cache": "paged", "page_size": 8, "pages": 10}
        server = engine.InferenceServer(
            cfg, params, plan=plan, max_len=48, max_batch=2,
            obs=obs.Observability(), **kwargs)
        sp = SamplingParams(temperature=0.8, top_k=12, max_tokens=5,
                            seed=11)
        for name, lens, gap in [("solo", (9,), 0),
                                ("batched", (4, 13, 7), 0),
                                ("streaming", (4, 13, 7, 9), 3)]:
            # fresh bundle per workload: registry metrics are cumulative
            # across serve() runs, and the reconciliation below compares
            # them against one run's engine stats
            server.attach_obs(obs.Observability())
            reqs = _reqs(cfg, lens, sp, gap=gap, seed=1)
            out = server.serve(reqs)
            _check_trace_properties(server, reqs, out)

    def test_preempted_lifecycle_and_front_order(self, llama):
        """The workload from test_cache's pool-exhaustion test: pages=7
        forces preemptions, and the trace must show them resumed in
        FRONT order with pages released."""
        cfg, params = llama
        server = engine.InferenceServer(
            cfg, params, max_len=32, max_batch=3, cache="paged",
            page_size=4, pages=7, obs=obs.Observability())
        sp = SamplingParams(temperature=0.6, top_k=10, max_tokens=8,
                            seed=3)
        reqs = _reqs(cfg, (4, 9, 6, 13), sp)
        out = server.serve(reqs)
        assert server.stats["preemptions"] > 0
        _check_trace_properties(server, reqs, out)
        # at least one lifecycle actually exercised the preempted arm
        assert any("preempted" in server.obs.tracer.lifecycle(u)
                   for u in server.obs.tracer.uids())
        assert server.obs.registry.counter(
            "serve_preemptions_total").value() == \
            server.stats["preemptions"]
        assert server.obs.registry.counter(
            "serve_pool_exhausted_total").value() >= \
            server.stats["preemptions"]

    def test_tokens_identical_with_and_without_obs(self, llama):
        cfg, params = llama
        sp = SamplingParams(temperature=0.7, top_k=9, max_tokens=6,
                            seed=5)
        plain = engine.InferenceServer(cfg, params, max_len=48,
                                       max_batch=2, cache="paged",
                                       page_size=8, pages=10)
        ref = plain.serve(_reqs(cfg, (4, 13, 7), sp, gap=2))
        plain.attach_obs(obs.Observability())
        traced = plain.serve(_reqs(cfg, (4, 13, 7), sp, gap=2))
        for uid in ref:
            np.testing.assert_array_equal(ref[uid], traced[uid])
        plain.attach_obs(None)
        again = plain.serve(_reqs(cfg, (4, 13, 7), sp, gap=2))
        for uid in ref:
            np.testing.assert_array_equal(ref[uid], again[uid])

    def test_metrics_snapshot_and_summary(self, llama):
        cfg, params = llama
        server = engine.InferenceServer(
            cfg, params, max_len=48, max_batch=2, cache="paged",
            page_size=8, pages=10, obs=obs.Observability())
        sp = SamplingParams(max_tokens=5)      # greedy path
        server.serve(_reqs(cfg, (4, 13, 7), sp))
        snap = server.metrics_snapshot()
        m, s = snap["metrics"], snap["summary"]
        assert s["requests"] == 3 and s["tokens"] == 15
        assert s["ttft_s"]["p50"] is not None
        assert s["token_latency_s"]["p99"] is not None
        assert sum(s["decode_width_steps"].values()) == \
            server.stats["decode_steps"]
        assert set(s["decode_compiles_per_width"]) == \
            set(s["decode_width_steps"])
        # cache gauges published from memory_report
        pages_gauge = [x for x in m["serve_cache_pages_in_use"]["series"]
                       if x["labels"] == {"backend": "paged"}]
        assert pages_gauge and pages_gauge[0]["value"] == 0
        assert m["serve_cache_peak_pages_in_use"]["series"][0]["value"] > 0
        # all-greedy workload took the greedy decode path only
        paths = {tuple(sorted(x["labels"].items()))
                 for x in m["serve_decode_steps_total"]["series"]}
        assert all(dict(p)["path"] == "greedy" for p in paths)
        # detached server returns {}
        server.attach_obs(None)
        assert server.metrics_snapshot() == {}

    def test_topk_skip_counter(self, llama):
        cfg, params = llama
        server = engine.InferenceServer(cfg, params, max_len=32,
                                        max_batch=2,
                                        obs=obs.Observability())
        # temperature>0 with top_k=0: sampled path, sort skipped
        server.serve(_reqs(cfg, (4, 6), SamplingParams(
            temperature=0.8, max_tokens=4, seed=1)))
        skipped = server.obs.registry.counter(
            "serve_topk_sort_steps_total", labels=("skipped",))
        assert skipped.value(skipped="true") > 0
        assert skipped.value(skipped="false") == 0
        # truncating top_k: sort needed
        server.serve(_reqs(cfg, (4, 6), SamplingParams(
            temperature=0.8, top_k=5, max_tokens=4, seed=1)))
        assert skipped.value(skipped="false") > 0
        rate = server.metrics_snapshot()["summary"]["topk_sort_skip_rate"]
        assert 0.0 < rate < 1.0


# ---------------------------------------------------------------------------
# exporters + validator
# ---------------------------------------------------------------------------

class TestValidateTool:
    def test_end_to_end_files(self, llama, tmp_path):
        cfg, params = llama
        server = engine.InferenceServer(
            cfg, params, max_len=32, max_batch=2, cache="paged",
            page_size=4, pages=12, obs=obs.Observability())
        server.serve(_reqs(cfg, (4, 9, 6), SamplingParams(
            temperature=0.6, top_k=8, max_tokens=4, seed=2), gap=2))
        mpath = tmp_path / "m.prom"
        tpath = tmp_path / "t.jsonl"
        spath = "tests/obs_schema.json"
        obs.write_prometheus(server.obs.registry, str(mpath))
        obs.write_trace(server.obs.tracer, str(tpath))
        assert obs_validate.validate_files(str(mpath), str(tpath),
                                           spath) == []
        assert obs_validate.main(["--metrics", str(mpath),
                                  "--trace", str(tpath),
                                  "--schema", spath]) == 0
        # corrupt one trace line -> validation fails
        lines = tpath.read_text().splitlines()
        bad = json.loads(lines[0])
        bad["kind"] = "teleported"
        lines[0] = json.dumps(bad)
        tpath.write_text("\n".join(lines) + "\n")
        errs = obs_validate.validate_files(str(mpath), str(tpath), spath)
        assert errs and any("enum" in e for e in errs)

    def test_prometheus_parser_rejects_bad_input(self):
        with pytest.raises(ValueError):
            obs_validate.parse_prometheus("orphan_metric 1\n")
        with pytest.raises(ValueError):
            obs_validate.parse_prometheus(
                "# TYPE h histogram\n"
                'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
                "h_sum 1\nh_count 3\n")     # non-cumulative buckets
        with pytest.raises(ValueError):
            obs_validate.parse_prometheus(
                "# TYPE h histogram\n"
                'h_bucket{le="+Inf"} 3\nh_sum 1\nh_count 4\n')

    def test_schema_checker_units(self):
        schema = json.load(open("tests/obs_schema.json"))
        ok = {"uid": 0, "kind": "decode", "t": 0.5, "n": 2}
        assert obs_validate.check_schema(ok, schema) == []
        assert obs_validate.check_schema(
            {"uid": 0, "kind": "decode"}, schema)       # missing t
        assert obs_validate.check_schema(
            {"uid": 0, "kind": "decode", "t": 0.5, "zz": 1}, schema)
        assert obs_validate.check_schema(
            {"uid": True, "kind": "decode", "t": 0.5}, schema)
        assert obs_validate.check_schema(
            {"uid": -1, "kind": "decode", "t": 0.5}, schema)


# ---------------------------------------------------------------------------
# profiler spans and named scopes
# ---------------------------------------------------------------------------

def test_import_obs_does_not_import_jax():
    import os
    import pathlib
    import subprocess
    import sys
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src)
    code = ("import sys, repro.obs; "
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'; "
            "assert callable(repro.obs.span)")
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


def test_decode_step_carries_named_scopes(llama):
    """The greedy paged decode step of a plan-quantized LM: its ops'
    metadata names the pool handling, the quantized linear, the layers
    and the sampler, before and after compilation."""
    cfg, params = llama
    plan = engine.synthetic_plan(cfg, params, seed=0)
    server = engine.InferenceServer(cfg, params, plan, cache="paged",
                                    max_len=32, max_batch=2, page_size=8)
    be = server.backend
    lowered = server._decode_greedy.lower(
        server.params, np.zeros((3,), np.int32), be.gather(),
        be.device_tables(), np.zeros((2,), np.int32), be.table_width)
    text = lowered.as_text(debug_info=True)
    for path in ("jit(decode_greedy)/kv_pool/", "/attn/kv_pool/",
                 "/layer0/attn/qlinear/", "/layer0/ffn/qlinear/",
                 "/layer1/", "jit(decode_greedy)/lm_head/",
                 "jit(decode_greedy)/sample/"):
        assert path in text, path
    compiled = lowered.compile().as_text()
    for scope in ("kv_pool", "qlinear"):
        assert re.search(
            rf'op_name="jit\(decode_greedy\)/\S*{scope}/', compiled), scope


class _MarkedNumpy:
    """``numpy`` as the engine module sees it, with every host readback
    of a device array (``np.asarray`` of a ``jax.Array``) marked by a
    ``test.readback`` span in the profiler trace."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def asarray(x, *args, **kwargs):
        if isinstance(x, jax.Array):
            with jax.profiler.TraceAnnotation("test.readback"):
                return np.asarray(x, *args, **kwargs)
        return np.asarray(x, *args, **kwargs)


def _host_spans(trace_dir):
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "test.")):
                    stats = {k: v for k, v in e.stats}
                    spans.append((e.name, e.start_ns, e.end_ns, stats,
                                  (plane.name, line.name)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return (inner[4] == outer[4] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


# blocking readbacks of one decode step, by decode path: the token vector
# (the tokens with the NaN flag) on the device-sampling paths, the logits
# on the host path
READBACKS_PER_DECODE = {"greedy": 1, "sample": 1, "host": 1}


@pytest.mark.parametrize("path", list(READBACKS_PER_DECODE))
def test_serve_spans_in_profiler_trace(llama, tmp_path, monkeypatch, path):
    cfg, params = llama
    server = engine.InferenceServer(
        cfg, params, cache="paged", max_len=32, max_batch=2, page_size=8,
        sample_on_device=path != "host")
    sp = (SamplingParams(max_tokens=4) if path != "sample" else
          SamplingParams(temperature=0.8, top_k=5, max_tokens=4, seed=1))
    # two requests at step 0, a third arriving while they decode
    server.begin(_reqs(cfg, [5, 9, 7], sp, gap=2))
    server.step()                        # compile outside the trace
    monkeypatch.setattr(engine, "np", _MarkedNumpy())
    jax.profiler.start_trace(str(tmp_path))
    results = []
    while server.has_work:
        results.append(server.step())
    jax.profiler.stop_trace()
    monkeypatch.undo()
    server.end()

    spans = _host_spans(tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == len(results)
    for s in spans:
        if s[0].startswith(("serve.admit", "serve.decode.")):
            assert any(_inside(s, st) for st in steps), s
    waits = [s for s in spans if s[0] == "serve.device_wait"]
    reads = [s for s in spans if s[0] == "test.readback"]
    # every blocking readback is inside exactly one device_wait span, and
    # every device_wait span holds exactly one readback
    assert reads and len(reads) == len(waits)
    for r in reads:
        assert sum(_inside(r, w) for w in waits) == 1, r
    for st, res in zip(steps, results):
        inner = [s for s in spans if _inside(s, st) and s is not st]
        names = [s[0] for s in inner]
        admits = [s for s in inner if s[0] == "serve.admit"]
        assert len(admits) == 1
        prefills = [s for s in inner if s[0] == "serve.prefill"]
        assert ([int(s[3]["uid"]) for s in prefills]
                == list(res.admitted))
        assert names.count("serve.sample_first") == len(res.admitted)
        # the decode's own readbacks: device_wait spans outside admission
        decode_waits = [s for s in inner if s[0] == "serve.device_wait"
                        and not any(_inside(s, a) for a in admits)]
        collects = [s for s in inner if s[0] == "serve.decode.collect"]
        if res.idle:
            assert "serve.decode.launch" not in names
            assert not decode_waits and not collects
        else:
            # a step dispatches at most one decode (none once every
            # request has its last token sampled) and reads back once
            assert names.count("serve.decode.launch") <= 1
            if "serve.decode.launch" in names:
                for name in ("serve.decode.inputs", "serve.bookkeep"):
                    assert names.count(name) == 1, name
            assert len(collects) == 1
            assert len(decode_waits) == READBACKS_PER_DECODE[path]
            assert all(_inside(w, collects[0]) for w in decode_waits)
            launch = [s for s in inner if s[0] == "serve.decode.launch"]
            if launch and path != "host":
                # the next step is dispatched before this readback
                assert launch[0][2] <= collects[0][1]
    assert any(r.admitted for r in results)
    assert any(not r.admitted and not r.idle for r in results)
