"""Run one cell of the chip benchmark once.

    python3 chipbench/cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json``.  Earlier lines (standard error) give set-up,
compile, window and check details; the last line of standard output is
one JSON object.  Without a TPU, or with fewer chips than the cell asks
for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import pathlib                                              # noqa: E402
import sys                                                  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch import compile_cache

    import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
        return 3
    spec = harness.load_cell(args.workload)
    if len(devs) < spec["cell"]["chips"]:
        log(f"the cell needs {spec['cell']['chips']} chips; JAX found "
            f"{len(devs)}")
        return 3
    log(f"compile cache: {compile_cache.enable()}")
    out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, log=log)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
