"""Ahead-of-time compiles of one served layer at the cells' real widths,
for a TPU v5e that is described, not attached.

One layer of each configuration (36 query and KV heads of 64, hidden
2304, FFN 5760, the full vocabulary) goes through the program's decode
step at the decode cells' batch of 64 and widest table (96 pages), and
through its paged prefill at the longest prompt of each mix.  The mixed
configuration's layer carries the plan's real channel groups.  Compiling
a whole ten-layer step is left to the chip.  Nothing runs, so nothing
here says anything about results or speed.

The topology is described inside a fixture, never at import.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import model
import traffic


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the program's kernel dispatch to the Pallas kernels."""
    from repro.kernels.paged_attention import ops as pops
    from repro.kernels.quant_matmul import ops as qops
    monkeypatch.setattr(pops, "_on_tpu", lambda: True)
    monkeypatch.setattr(qops, "_on_tpu", lambda: True)


def layer_params(conf, sharding):
    """Abstract one-layer parameters; planned projections as
    ``PackedLinear`` of the plan's layer-0 channel groups."""
    from repro.models import lm
    from repro.nn.quantized import PackedLinear
    cfg = dataclasses.replace(model.arch(conf), n_layers=1)
    tree = lm.abstract_params(cfg)
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                         sharding=sharding)
    tree = jax.tree.map(put, tree)
    if conf["plan"] is None:
        return cfg, tree
    full = model.arch(conf)
    bits = model.plan_bits(full, conf["plan"])
    blk = {k: (v if not isinstance(v, dict) else dict(v))
           for k, v in tree["blocks"]["l0"].items()}
    for g, b in bits.items():
        if not g.endswith(".sb0"):
            continue
        _, _, part, proj, _ = g.split(".")
        w = tree["blocks"]["l0"][part][proj]["w"]
        k, n = w.shape[1], w.shape[2]
        groups = []
        for nb in (2, 4, 8):
            c = int(np.sum(b == nb))
            if c:
                groups.append((nb, put(jax.ShapeDtypeStruct(
                    (c, k * nb // 8), jnp.int8)),
                    put(jax.ShapeDtypeStruct((c,), jnp.float32))))
        kept = int(np.sum(b > 0))
        blk[part] = dict(blk[part])
        blk[part][proj] = {"w": PackedLinear(
            tuple(groups), put(jax.ShapeDtypeStruct((kept,), jnp.int32)),
            k, n)}
    norms = {k: jax.tree.map(lambda a: put(jax.ShapeDtypeStruct(
        a.shape[1:], a.dtype)), v) for k, v in blk.items()
        if k.startswith("norm")}
    blk.update(norms)
    out = dict(tree)
    out["blocks"] = ({"l0": blk},)
    return cfg, out


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("config", ["minicpm-2b-float", "minicpm-2b-mixed"])
def test_decode_layer_compiles(one_chip, on_tpu, config):
    from repro.models import lm
    mix = traffic.load_mix("decode")
    cfg, params = layer_params(model.load_config(config), one_chip)
    b, tw = mix["max_batch"], mix["max_len"] // mix["page_size"]
    caches = jax.tree.map(
        lambda s: spec(one_chip, s.shape, s.dtype),
        lm.init_paged_caches(cfg, b, mix["page_size"], b * tw,
                             abstract=True))

    def step(p, tok, c, tbl, pos):
        logits, c = lm.decode_step(cfg, p, {"tokens": tok}, c, pos,
                                   tables=tbl)
        return jnp.argmax(logits[:, -1, :cfg.vocab], -1), c

    text = jax.jit(step).lower(
        params, spec(one_chip, (b, 1), jnp.int32), caches,
        spec(one_chip, (b, tw), jnp.int32),
        spec(one_chip, (b,), jnp.int32)).compile().as_text()
    n = text.count("tpu_custom_call")
    assert n >= 1 + (0 if config.endswith("float") else 7)


@pytest.mark.parametrize("config,mix_name", [
    ("minicpm-2b-float", "decode"), ("minicpm-2b-mixed", "prefill")])
def test_prefill_layer_compiles(one_chip, on_tpu, config, mix_name):
    from repro.launch import steps
    from repro.models import lm
    mix = traffic.load_mix(mix_name)
    cfg, params = layer_params(model.load_config(config), one_chip)
    s = max(mix["prompt_lengths"])
    b, tw = mix["max_batch"], mix["max_len"] // mix["page_size"]
    caches = jax.tree.map(
        lambda x: spec(one_chip, x.shape, x.dtype),
        lm.init_paged_caches(cfg, b, mix["page_size"], b * tw,
                             abstract=True))
    width = -(-s // mix["page_size"])
    text = jax.jit(steps.make_paged_prefill_step(cfg)).lower(
        params, {"tokens": spec(one_chip, (1, s), jnp.int32)}, caches,
        spec(one_chip, (1, width), jnp.int32),
        spec(one_chip, (1,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
