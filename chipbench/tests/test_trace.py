"""The trace reduction on a small hand-made trace."""
import pytest

from trace_reduce import Event, reduce_events

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur, label=None):
    return Event(plane, line, name, float(start), float(dur),
                 label if label is not None else name)


def table():
    return [
        # two harness step spans: the window is [100, 1100)
        ev(HOST, "python", "chipbench.step", 100, 400),
        ev(HOST, "python", "chipbench.step", 600, 500),
        ev(HOST, "python", "PjitFunction(decode_greedy)", 620, 30),
        # device programs, one overlapping the window's start
        ev(DEV, "XLA Modules", "jit_prefill_paged(7)", 50, 250),
        ev(DEV, "XLA Modules", "jit_decode_greedy(9)", 700, 300),
        # ops: overlapping pairs count once in the busy union
        ev(DEV, "XLA Ops", "fusion.1", 50, 150),            # 100..200 in
        ev(DEV, "XLA Ops", "custom-call.3", 150, 150,
           "custom-call.3 _qmm_kernel"),                    # 150..300
        ev(DEV, "XLA Ops", "custom-call.9", 700, 200,
           "custom-call.9 _paged_attn_kernel"),             # 700..900
        ev(DEV, "XLA Ops", "fusion.2", 850, 150),           # 850..1000
        ev(DEV, "XLA Ops", "fusion.3", 1200, 50),           # outside
    ]


def test_window_busy_and_idle():
    r = reduce_events(table(), "chipbench.step")
    assert (r.t0, r.t1) == (100, 1100)
    assert r.window_s == pytest.approx(1000e-9)
    # busy: [100, 300) and [700, 1000) -> 500 ns of 1000
    assert r.busy_s == pytest.approx(500e-9)
    assert r.gaps == [(300, 700), (1000, 1100)]


def test_programs_and_kernels():
    r = reduce_events(table(), "chipbench.step")
    progs = r.programs()
    assert progs["decode_greedy"] == (1, pytest.approx(300e-9))
    assert progs["prefill_paged"] == (1, pytest.approx(250e-9))
    assert r.kernel("qmm") == (1, pytest.approx(150e-9))
    assert r.kernel("paged_attn") == (1, pytest.approx(200e-9))
    assert r.kernel("nothing") == (0, 0.0)


def test_breakdown_names_gaps_by_host_span():
    r = reduce_events(table(), "chipbench.step")
    b = r.breakdown()
    # ops are grouped by instruction name without its numbering
    assert b["device_ops"][0] == ["custom-call", pytest.approx(350e-9)]
    # the longest gap, [300, 700), has its middle at 500: inside the
    # first step span only
    assert b["idle_gaps"][0] == ["chipbench.step", pytest.approx(400e-9)]
    assert b["idle_gaps"][1] == ["chipbench.step", pytest.approx(100e-9)]


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        reduce_events([e for e in table() if e.name != "chipbench.step"],
                      "chipbench.step")


def recorded_step():
    """One decode step of ``minicpm2b-mixed.decode`` on a TPU v5e as the
    profiler recorded it (the harness span, the device's program and op
    lines; op names cut to 240 characters)."""
    import gzip
    import json
    import pathlib
    path = pathlib.Path(__file__).parent / "data" / "mixed_decode_step.json.gz"
    rows = json.loads(gzip.decompress(path.read_bytes()))
    return [Event(p, l, n, float(s), float(d), n) for p, l, n, s, d in rows]


def test_recorded_step():
    import importlib.util
    import pathlib
    r = reduce_events(recorded_step(), "chipbench.step")
    assert 0 < r.busy_s <= r.window_s
    n, secs = r.programs()["decode_greedy"]
    assert n == 1 and 0 < secs <= r.window_s
    readers = pathlib.Path(__file__).parents[1] / "layer_metrics"
    pats = {}
    for name in ("qlinear_roofline", "paged_decode_roofline"):
        spec = importlib.util.spec_from_file_location(
            name, readers / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        pats[name] = mod.KERNEL
    # 7 projections x 10 layers x 3 bit groups; one attention per layer
    assert r.kernel(pats["qlinear_roofline"])[0] == 210
    assert r.kernel(pats["paged_decode_roofline"])[0] == 10
    kinds = [k for k, _ in r.breakdown()["device_ops"]]
    assert "copy" in kinds
