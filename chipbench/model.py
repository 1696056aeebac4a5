"""A configuration as it is run: sizes from ``configs/<name>.json``,
weights and channel plan made here from seeds.

The weights are the benchmark's own (the plain reference reads the same
tree), laid out as the program's parameter tree so the server can take
them.  They are made on the device in one jitted call, in bf16.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    conf = json.loads(path.read_text())
    if conf.get("name") != name:
        raise ValueError(f"{path} names itself {conf.get('name')!r}")
    return conf


def arch(conf: dict):
    """The program's ArchConfig for this configuration."""
    from repro.configs import registry
    return dataclasses.replace(registry.get(conf["arch"]),
                               **conf["overrides"])


def seed_key(seed: int):
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    the low 32 bits, so the high ones are folded in)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _path_name(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def _scale(name: str, shape) -> float:
    if name in ("embed.w", "lm_head.w"):
        return 0.02
    if "norm" in name.rsplit(".", 1)[-1]:
        return 0.1
    return float(1.0 / np.sqrt(shape[-2]))


def make_params(cfg, seed: int):
    """Seeded bf16 weights in the program's tree layout, made on the
    device by one jitted call (compiled once per configuration: the seed
    is an argument, not a constant)."""
    from repro.models import lm
    tmpl = lm.abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tmpl)

    def gen(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            s = _scale(_path_name(path), leaf.shape)
            x = jax.random.normal(k, leaf.shape, jnp.float32) * s
            out.append(x.astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(gen)(seed_key(seed))


def plan_groups(cfg) -> dict:
    """Plan group name -> output channel count, for every projection that
    the program's plan binding quantizes (one group per layer)."""
    from repro.models import lm
    tmpl = lm.abstract_params(cfg, mps_on=True)["blocks"]
    groups = {}

    def visit(node, path):
        if "gamma" in node and "w" in node and node["w"].ndim == 3:
            for j in range(node["w"].shape[0]):
                groups[f"{path}.sb{j}"] = int(node["w"].shape[2])
            return
        for k, v in node.items():
            if isinstance(v, dict):
                visit(v, f"{path}.{k}")

    for lname, node in tmpl.items():
        visit(node, f"blocks.{lname}")
    return groups


def plan_bits(cfg, spec: dict, ref=None) -> dict:
    """Per-channel bits for every plan group, drawn from the plan seed
    with probabilities ``p`` over ``pw`` (fixed by the configuration, so
    every run serves the same plan).  The groups are the reference
    module ``ref``'s ``plan_groups`` where it has one, else
    :func:`plan_groups`, drawn in sorted order."""
    groups = getattr(ref, "plan_groups", plan_groups)(cfg)
    pw = np.asarray(spec["pw"], np.int64)
    p = np.asarray(spec["p"], np.float64)
    p = p / p.sum()
    rng = np.random.default_rng(int(spec["seed"]))
    return {g: rng.choice(pw, size=n, p=p).astype(np.int64)
            for g, n in sorted(groups.items())}


def make_plan(conf: dict, bits: dict):
    from repro.api.plan import CompressionPlan
    assignment = {"gamma": bits, "delta": {}, "alpha": {}}
    return CompressionPlan.from_assignment(
        assignment, conf["plan"]["pw"], (8,),
        meta={"track": "lm", "arch": conf["arch"], "synthetic": True,
              "seed": conf["plan"]["seed"]})

