"""A new traffic mix, cell, per-layer metric and configuration (with its
own plain reference and work counts) need only new files and new
entries; and the entry point refuses to run without a chip."""
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def digest(tree: pathlib.Path) -> dict:
    return {str(p.relative_to(tree)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(tree.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def env(**extra):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    e.pop("PYTHONPATH", None)
    return e


@pytest.fixture
def copy(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_new_mix_cell_and_metric_need_no_edit(copy):
    before = digest(copy / "chipbench")
    mix = json.loads((copy / "chipbench/traffic/decode.json").read_text())
    mix.update(clients=32, prompt_lengths=[256, 2048], max_len=2560,
               prompt_weights=[3, 1], block=40)
    (copy / "chipbench/traffic/long_chat.json").write_text(json.dumps(mix))
    (copy / "chipbench/limits/minicpm2b-float.long_chat.json").write_text(
        json.dumps({"logit_gap": 0.5}))
    (copy / "chipbench/layer_metrics/steps_per_s.py").write_text(
        "def read(ctx):\n    return len(ctx.steps) / ctx.trace.window_s\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "minicpm2b-float.long_chat", "config": "minicpm-2b-float",
         "traffic": "long_chat", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "steps_per_s", "unit": "1/s", "better": "higher",
         "source": "host_clock", "layer": "scheduler and engine",
         "moves": "output_tok_s",
         "workloads": ["minicpm2b-float.long_chat"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import sys; sys.path.insert(0, 'chipbench'); import harness\n"
        "s = harness.load_cell('minicpm2b-float.long_chat')\n"
        "r = harness.layer_readers([m['name'] for m in s['per_layer']])\n"
        "print(s['mix']['clients'], s['conf']['name'], sorted(r),"
        " [m['name'] for m in s['end_to_end']])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "32 minicpm-2b-float" in out.stdout
    assert "['steps_per_s']" in out.stdout   # metrics list their cells
    assert "itl_p95_ms" not in out.stdout     # listed for the decode cells
    after = digest(copy / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before


REF_PROBE = """\
import reference

calls = []


def _counted(name):
    def call(*args, **kw):
        calls.append(name)
        return getattr(reference, name)(*args, **kw)
    return call


dims, stack_bits, hidden, logits = map(
    _counted, ("dims", "stack_bits", "hidden", "logits"))
"""

WORK_PROBE = """\
from work import paged_decode_bytes, qlinear_roofline_s  # noqa: F401

FLOPS = 4.925e12


def model_flops(cfg, bits, prefill, decode_ctx):
    return FLOPS
"""

SMOKE_MIX = {"loop": "closed", "clients": 4, "max_batch": 4, "max_len": 64,
             "page_size": 16, "pages": 16, "prompt_lengths": [16, 32],
             "prompt_weights": [1, 1], "output_min": 4, "output_max": 8,
             "block": 4, "greedy": True, "check_requests": 3}

PROBE_RUN = f"""\
import json, sys, time
sys.path[:0] = [{str(ROOT / "src")!r}, "chipbench"]
import harness, model, trace_reduce as tr
s = harness.load_cell("probe.smoke")
out = harness.run(s, 3, 1.0, False, t_start=time.perf_counter(),
                  require_tpu=False, log=lambda _: None)
# one step of 1 s, read by serve.mfu through the configuration's counts
ev = [tr.Event("/host:CPU", "python", "chipbench.step", 0.0, 1e9),
      tr.Event("/device:TPU:0", "XLA Ops", "fusion.1", 0.0, 5e8)]
ctx = tr.Context(trace=tr.reduce_events(ev, "chipbench.step"),
                 steps=[harness.StepRec(0.0, 1.0, [16], [20, 30], 3)],
                 cfg=model.arch(s["conf"]), bits=None,
                 peaks=harness.work.peaks("TPU v5 lite"), work=s["work"])
mfu = harness.layer_readers(["serve.mfu"])["serve.mfu"].read(ctx)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"],
                  "calls": sorted(set(s["reference"].calls)),
                  "ref": s["reference"].__file__, "mfu": mfu}}))
"""


def test_new_configuration_needs_no_edit(copy):
    """A configuration that names its own reference and work modules is
    rehearsed, checked and counted through them, with no file of the
    benchmark edited."""
    before = digest(copy / "chipbench")
    bench_dir = copy / "chipbench"
    conf = json.loads((bench_dir / "configs/minicpm-2b-mixed.json")
                      .read_text())
    conf.update(name="probe", arch="minicpm-2b-smoke",
                overrides={"param_dtype": "bfloat16"},
                reference="ref_probe", work="work_probe")
    (bench_dir / "configs/probe.json").write_text(json.dumps(conf))
    (bench_dir / "ref_probe.py").write_text(REF_PROBE)
    (bench_dir / "work_probe.py").write_text(WORK_PROBE)
    (bench_dir / "traffic/probe_smoke.json").write_text(json.dumps(SMOKE_MIX))
    (bench_dir / "limits/probe.smoke.json").write_text(
        json.dumps({"logit_gap": 0.02}))
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "probe.smoke", "config": "probe", "traffic": "probe_smoke",
         "chips": 1, "why": "test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run([sys.executable, "-c", PROBE_RUN], cwd=copy,
                         env=env(), capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["ref"] == str(bench_dir / "ref_probe.py")
    assert res["calls"] == ["dims", "hidden", "logits", "stack_bits"]
    assert res["mfu"] == pytest.approx(100.0 * 4.925e12 / 197e12)
    after = digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before


def test_no_chip_no_result(copy):
    argv = [sys.executable, "chipbench/cell.py", "--workload",
            "minicpm2b-float.decode", "--seed", "1", "--seconds", "1",
            "--trace", "0"]
    out = subprocess.run(argv, cwd=ROOT, env=env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    # a checkout holding only BENCHMARK.json and the benchmark's files
    out = subprocess.run(argv, cwd=copy, env=env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
