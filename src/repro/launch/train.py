"""Distributed training launcher.

On real hardware this runs under the production mesh (16x16 per pod); on
this CPU container it runs reduced configs on a debug mesh — same code
path, same step functions as the dry-run.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b-smoke \
        --steps 50 [--search] [--ckpt-dir /tmp/ckpt]

Fault tolerance: atomic step-tagged checkpoints + auto-resume; SIGTERM
triggers a final checkpoint before exit (preemption-safe). Straggler
mitigation on real pods: fixed-shape steps (no data-dependent shapes
anywhere) + the XLA latency-hiding scheduler flag below.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time

# overlap compute with collectives on TPU (no-op on CPU)
os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_tpu_enable_latency_hiding_scheduler=true")

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.checkpoint.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import registry                      # noqa: E402
from repro.data import synthetic                        # noqa: E402
from repro.distributed import sharding                  # noqa: E402
from repro.launch import compile_cache                  # noqa: E402
from repro.launch import mesh as meshlib                # noqa: E402
from repro.launch import steps as steps_lib             # noqa: E402
from repro.models import lm                             # noqa: E402
from repro.optim import optimizers                      # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--search", action="store_true",
                    help="joint MPS+pruning objective (paper Sec. 4)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (needs 256 devices)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = registry.get(args.arch)
    n_dev = len(jax.devices())
    if args.production_mesh:
        mesh = meshlib.make_production_mesh()
    else:
        mesh = meshlib.make_debug_mesh(data=1, model=1)
    rules = dict(registry.RULE_OVERRIDES.get(cfg.name.replace("-smoke", ""),
                                             {}))
    rules.update(steps_lib.shape_rules(
        type("S", (), {"kind": "train", "global_batch": args.batch})()))

    with sharding.use_mesh(mesh, rules):
        params = lm.init_params(cfg, jax.random.key(0), mps_on=args.search)
        opt = optimizers.make_optimizer(cfg.optimizer, 3e-4)
        opt_state = opt.init(params)
        step_fn = jax.jit(steps_lib.make_train_step(cfg, opt,
                                                    search=args.search))

        mgr = None
        state = {"params": params, "opt": opt_state}
        start = 0
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=2)
            restored, meta = mgr.restore_latest(state)
            if restored is not None:
                state, start = restored, meta["step"] + 1
                print(f"[train] resumed from step {meta['step']}")

        stop = {"flag": False}
        signal.signal(signal.SIGTERM, lambda *_: stop.update(flag=True))

        t0 = time.time()
        loss = float("nan")
        for step in range(start, args.steps):
            batch = synthetic.lm_batch(cfg.vocab, args.seq + 1, args.batch,
                                       step)
            new_p, new_o, loss = step_fn(state["params"], state["opt"],
                                         batch, jnp.asarray(step))
            state = {"params": new_p, "opt": new_o}
            if step % 10 == 0:
                print(f"[train] step {step} loss {float(loss):.4f} "
                      f"({time.time()-t0:.1f}s, {n_dev} devices)")
            if mgr and (step % args.ckpt_every == 0 and step > start
                        or stop["flag"]):
                mgr.save(step, state, blocking=stop["flag"])
            if stop["flag"]:
                print("[train] SIGTERM: checkpointed, exiting")
                sys.exit(0)
        if mgr:
            mgr.wait()
            mgr.save(args.steps - 1, state)
        print(f"[train] done: final loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
