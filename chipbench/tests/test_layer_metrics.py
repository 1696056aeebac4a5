"""The reader of the program's own spans (``engine.host_ms``) on small
hand-made traces: the value it reads, and nothing on a trace without
the spans (a program that lacks them)."""
import importlib.util
import pathlib

import pytest

from test_trace import recorded_step
from trace_reduce import Context, Event, reduce_events

DEV = "/device:TPU:0"
HOST = "/host:CPU"
PY = "python"


def reader(name):
    path = pathlib.Path(__file__).parents[1] / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur), name)


def table():
    return [
        # two harness steps; the window is [0, 2000)
        ev(HOST, PY, "chipbench.step", 0, 1000),
        ev(HOST, PY, "chipbench.step", 1000, 1000),
        # the program's spans: steps of 900 and 800 ns that waited on
        # the device 300 and 100 + 200 ns
        ev(HOST, PY, "serve.step", 50, 900),
        ev(HOST, PY, "serve.admit", 60, 200),
        ev(HOST, PY, "serve.device_wait", 600, 300),
        ev(HOST, PY, "serve.step", 1100, 800),
        ev(HOST, PY, "serve.device_wait", 1200, 100),
        ev(HOST, PY, "serve.device_wait", 1400, 200),
        # a wait on another thread is not the step's
        ev(HOST, "other", "serve.device_wait", 1100, 700),
        ev(DEV, "XLA Modules", "jit_decode_greedy(1)", 100, 400),
        ev(DEV, "XLA Ops", "fusion.1", 100, 400),
    ]


def ctx_of(events):
    return Context(trace=reduce_events(events, "chipbench.step"),
                   steps=[], cfg=None, bits=None, peaks={}, work=None)


def test_host_ms_is_the_step_less_its_device_waits():
    # ((900 - 300) + (800 - 300)) / 2 ns a step
    assert reader("engine.host_ms").read(ctx_of(table())) == \
        pytest.approx(550e-6)


def test_host_ms_reads_nothing_without_spans():
    stripped = [e for e in table() if not e.name.startswith("serve.")]
    assert reader("engine.host_ms").read(ctx_of(stripped)) is None
    # a decode step recorded on the chip before the program had spans
    assert reader("engine.host_ms").read(ctx_of(recorded_step())) is None
