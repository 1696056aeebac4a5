"""A new traffic mix, cell and per-layer metric need only new files and
new entries; and the entry point refuses to run without a chip."""
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
