"""The control of the logit check, on the chip at a cell's own size.

    python3 chipbench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, one whole run of the cell (as ``cell.py`` makes it) whose
check also reads the control: the plain reference one precision lower
(``reference.py``, ``lowp``) put in the program's place, judged at every
position of the same sampled requests by the gap of the token it puts
first.  The control has to read above the cell's limit on every seed;
the program's own gaps on the same seeds are lower readings.  Prints
one JSON line per seed and a last line with the smallest control gap
and the largest served gap; exits 1 if any control reads within the
limit.  A benchmark run never runs this.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    compile_cache.enable()
    spec = harness.load_cell(args.workload)
    served, ctl = [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run(spec, seed, args.seconds, False, t_start=t0,
                          control=True, log=log)
        c = out["checks"]
        served.append(c["logit_gap"]["value"])
        ctl.append(c["control_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "served_gap": served[-1],
                          "control_gap": ctl[-1]}), flush=True)
    limit = spec["limits"]["logit_gap"]
    print(json.dumps({"workload": args.workload, "limit": limit,
                      "control_min": min(ctl), "served_max": max(served),
                      "seeds": args.seeds}), flush=True)
    return 0 if min(ctl) > limit else 1


if __name__ == "__main__":
    sys.exit(main())
