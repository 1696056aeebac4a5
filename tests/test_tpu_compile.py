"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode runs the kernels' math on the CPU but not Mosaic's
lowering, which refuses what interpret mode accepts (an
``optimization_barrier``, a vector shape cast it cannot lay out, a block
that is not tile-aligned, too much VMEM).  These tests compile each
kernel at real serving widths for a v5e that is described, not
attached, and check that the program holds the kernel
(``tpu_custom_call``).  They also compile the serving engine's paged
decode and prefill programs and check that no op in them writes a
buffer the size of a layer's page pool other than the in-place page
writes: the pool is addressed where it lies, never sliced, restacked or
relaid out.  Nothing runs, so nothing here says anything about results
or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.paged_attention import kernel as pk
from repro.kernels.paged_attention import ops as pops
from repro.kernels.paged_attention import prefill as pf
from repro.kernels.quant_matmul import kernel as qk
from repro.kernels.quant_matmul import ops as qops
from repro.kernels.ssm_decode import kernel as sk
from repro.kernels.ssm_decode import ops as sops
from repro.launch import steps
from repro.models import lm
from repro.nn.quantized import PackedLinear
from repro.serve import engine

PAGE = 16
N_PAGES = 64
TABLE_WIDTH = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise log under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# llama3.2-1b (32/8 heads of 64) and a gemma2-style windowed, soft-capped
# head_dim-256 layer (8/4 heads, window 4096, cap 50)
ATTN_CASES = {
    "llama3.2-1b-bf16": dict(h=32, hkv=8, d=64, dtype=jnp.bfloat16),
    "llama3.2-1b-f32": dict(h=32, hkv=8, d=64, dtype=jnp.float32),
    "gemma2-window-softcap": dict(h=8, hkv=4, d=256, dtype=jnp.bfloat16,
                                  window=4096, cap=50.0),
}


@pytest.mark.parametrize("case", list(ATTN_CASES))
def test_paged_decode_compiles(one_chip, case):
    c = dict(ATTN_CASES[case])
    h, hkv, d, dt = c.pop("h"), c.pop("hkv"), c.pop("d"), c.pop("dtype")
    pool = spec(one_chip, (N_PAGES + 1, PAGE, hkv * d), dt)
    text = compile_text(
        functools.partial(pk.paged_attention_fwd, interpret=False, **c),
        spec(one_chip, (4, h, d), dt), pool, pool,
        spec(one_chip, (4, TABLE_WIDTH), jnp.int32),
        spec(one_chip, (4,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case", list(ATTN_CASES))
def test_paged_prefill_compiles(one_chip, case):
    c = dict(ATTN_CASES[case])
    h, hkv, d, dt = c.pop("h"), c.pop("hkv"), c.pop("d"), c.pop("dtype")
    pool = spec(one_chip, (N_PAGES + 1, PAGE, hkv * d), dt)
    text = compile_text(
        functools.partial(pf.paged_prefill_fwd, interpret=False,
                          q_chunk=16, **c),
        spec(one_chip, (1, 208, h, d), dt), pool, pool,
        spec(one_chip, (1, 13), jnp.int32),
        spec(one_chip, (1,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("m", [8, 128])
def test_quant_matmul_compiles(one_chip, bits, m):
    """The llama3.2-1b up projection (N 8192, K 2048) at a decode-sized
    and a prefill-sized M, blocked as ``ops.quant_matmul`` blocks it."""
    n, k = 8192, 2048
    per = 8 // bits
    kp = k // per
    text = compile_text(
        functools.partial(qk.quant_matmul_fwd, w_bits=bits, bm=min(128, m),
                          bk=min(qk.DEFAULT_BK, kp), interpret=False),
        spec(one_chip, (per, m, kp), jnp.int8),
        spec(one_chip, (n, kp), jnp.int8),
        spec(one_chip, (1, n), jnp.float32),
        spec(one_chip, (1, 1), jnp.float32))
    assert "tpu_custom_call" in text


# --- the engine's paged programs hold no whole-pool copy ------------------

SERVE_PAGES = 1024
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8}
# ops that may carry a whole pool: the donated parameters, tuples of
# them, reinterpretations, the scanned runner's loop, and the in-place
# page writes (a scatter, alone or as a fusion's root)
_POOL_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "scatter"}


def _largest_output(shape: str) -> int:
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
             * _DTYPE_BYTES[dt]
             for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", shape)
             if dt in _DTYPE_BYTES]
    return max(sizes, default=0)


def pool_sized_ops(hlo: str, pool_bytes: int, allowed=_POOL_OPS) -> list:
    """``(computation, opcode, name)`` of every op outside a fusion body
    that outputs a buffer of at least ``pool_bytes`` and is not in
    ``allowed``: a copy, slice, dynamic-slice, concatenate, relayout or
    fusion (other than a scatter's) that moves a whole pool."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.strip().startswith(("%", "ROOT")):
            cur.append(line.strip())
    fused = {c for lines in comps.values() for line in lines
             for c in re.findall(r"calls=%?([\w.\-]+)", line)}
    found = []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            op = re.match(r"(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(",
                          line)
            if op is None or _largest_output(op.group(2)) < pool_bytes:
                continue
            name, _, code = op.groups()
            if code == "fusion":
                body = comps[re.search(r"calls=%?([\w.\-]+)",
                                       line).group(1)]
                if any(" scatter(" in b for b in body):
                    continue
            if code not in allowed:
                found.append((comp, code, name))
    return found


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


@pytest.fixture(scope="module")
def two_layer_lm():
    """llama3.2-1b's pattern at two layers, 4/2 heads of 64: the
    unaligned head width (64 of a 128-lane tile)."""
    cfg = dataclasses.replace(registry.get("llama3.2-1b-smoke"),
                              head_dim=64)
    return cfg, lm.init_params(cfg, jax.random.key(0))


@pytest.mark.parametrize("program", ["decode_greedy", "prefill_paged"])
@pytest.mark.parametrize("runner", ["scanned-float", "unrolled-plan"])
def test_paged_programs_hold_no_pool_copy(one_chip, two_layer_lm,
                                          monkeypatch, runner, program):
    cfg, params = two_layer_lm
    plan = engine.synthetic_plan(cfg, params, seed=0) \
        if runner == "unrolled-plan" else None
    server = engine.InferenceServer(cfg, params, plan, cache="paged",
                                    max_len=256, max_batch=4,
                                    page_size=PAGE, pages=SERVE_PAGES)
    be = server.backend
    # the engine picks its kernels by the default backend, which here is
    # the CPU: steer it to the TPU kernels for this compile
    monkeypatch.setattr(pops, "_on_tpu", lambda: True)
    monkeypatch.setattr(qops, "_on_tpu", lambda: True)
    args = {
        "decode_greedy": lambda: server._decode_greedy.lower(
            *_abstract((server.params, np.zeros((5,), np.int32),
                        be.gather(), be.device_tables(),
                        np.zeros((4,), np.int32)), one_chip),
            be.table_width),
        "prefill_paged": lambda: server._prefill_paged.lower(
            *_abstract((server.params, {"tokens": np.zeros((1, 64),
                                                           np.int32)},
                        be.kv_caches(), be.device_tables(), np.int32(0),
                        np.asarray([60], np.int32)), one_chip),
            64 // PAGE),
    }
    hlo = args[program]().compile().as_text()
    assert "tpu_custom_call" in hlo
    k_pool = be.kv_caches()["l0"]["kv"]["k"]
    layer_bytes = k_pool[0].size * k_pool.dtype.itemsize
    assert layer_bytes >= SERVE_PAGES * PAGE * cfg.head_dim * 2
    assert pool_sized_ops(hlo, layer_bytes) == []


# --- granite-4.0-h-small: the Mamba-2 decode-state kernel and one stage --

@pytest.mark.parametrize("shape", [(64, 128, 64, 128), (64, 128, 128, 128)],
                         ids=["granite", "jamba"])
def test_ssm_decode_compiles(one_chip, shape):
    """The decode cell's 64 rows: granite's 128 heads of 64 and a
    head_dim-128 (jamba) state, d_state 128."""
    b, h, p, n = shape
    f32 = jnp.float32
    text = compile_text(
        functools.partial(sk.ssm_decode_step_fwd, interpret=False),
        spec(one_chip, shape, f32), spec(one_chip, (b, h, p), f32),
        spec(one_chip, (b, h), f32), spec(one_chip, (h,), f32),
        spec(one_chip, (b, n), f32), spec(one_chip, (b, n), f32),
        spec(one_chip, (h,), f32))
    assert "tpu_custom_call" in text and "ssm_decode_step" in text


def granite_stage(n_layers=2):
    """granite-4.0-h-small at full width, cut to a stage of Mamba-2
    layers and one attention layer, each followed by its MoE of 9 held
    experts and the shared expert."""
    return dataclasses.replace(
        registry.get("granite-4.0-h-small"), n_layers=n_layers,
        attn_every=n_layers, n_experts_held=9)


def abstract_served_params(cfg, runner, sharding):
    """Abstract parameters as the server holds them: stacked float for
    the scanned runner, or the unrolled tree of ``apply_plan`` with every
    planned projection a ``PackedLinear`` of 0/2/4/8-bit channels in the
    ratio 1:2:3:4."""
    put = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=sharding)
    tmpl = lm.abstract_params(cfg, mps_on=runner == "unrolled-plan")
    params = {k: jax.tree.map(lambda a: put(a.shape, a.dtype), v)
              for k, v in tmpl.items() if k != "blocks"}
    if runner == "scanned-float":
        params["blocks"] = jax.tree.map(lambda a: put(a.shape, a.dtype),
                                        tmpl["blocks"])
        return params

    def bind(node):
        if "gamma" in node and node["w"].ndim == 3:
            k, n = node["w"].shape[1:]
            counts = [(b, n * r // 10) for b, r in ((2, 2), (4, 3), (8, 4))]
            return {"w": PackedLinear(
                tuple((b, put((c, k * b // 8), jnp.int8),
                       put((c,), jnp.float32)) for b, c in counts),
                put((sum(c for _, c in counts),), jnp.int32), k, n)}
        return {key: bind(v) if isinstance(v, dict)
                else put(v.shape[1:], v.dtype)
                for key, v in node.items() if key != "gamma"}

    # every super-block's tree has the same shapes
    params["blocks"] = tuple(bind(tmpl["blocks"])
                             for _ in range(lm.n_superblocks(cfg)))
    return params


@pytest.mark.parametrize("runner", ["scanned-float", "unrolled-plan"])
def test_granite_decode_updates_state_in_place(one_chip, monkeypatch,
                                               runner):
    """The decode cell's step (64 rows, the widest table of its 1,536
    positions, 3,072 pages): the Mamba-2 state goes through
    ``ssm_decode_step`` in place, and no op copies a whole layer's state
    or KV pool."""
    cfg = granite_stage()
    params = abstract_served_params(cfg, runner, one_chip)
    for mod in (pops, qops, sops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    caches = jax.tree.map(
        lambda a: spec(one_chip, a.shape, a.dtype),
        lm.init_paged_caches(cfg, 64, PAGE, 3072, abstract=True))

    def decode_greedy(p, tok, c, tbl, pos):
        logits, c = lm.decode_step(cfg, p, {"tokens": tok}, c, pos,
                                   tables=tbl)
        return jnp.argmax(logits[:, -1, :cfg.vocab], -1), c

    hlo = jax.jit(decode_greedy, donate_argnums=(2,)).lower(
        params, spec(one_chip, (64, 1), jnp.int32), caches,
        spec(one_chip, (64, 1536 // PAGE), jnp.int32),
        spec(one_chip, (64,), jnp.int32)).compile().as_text()
    assert hlo.count("custom-call(") >= 2
    assert re.search(r"%ssm_decode_step[.\d]* = ", hlo)
    pool = caches["l1"]["kv"]["k"]
    layer_bytes = pool.size // pool.shape[0] * 2          # bf16, < state
    assert pool_sized_ops(hlo, layer_bytes,
                          _POOL_OPS | {"custom-call"}) == []


def test_granite_prefill_compiles(one_chip, monkeypatch):
    """The decode mix's longest prompt (1,024 tokens) through the planned
    stage's paged prefill: the chunked SSD at chunk 256 and the paged
    prefill kernel fit the chip."""
    cfg = granite_stage()
    params = abstract_served_params(cfg, "unrolled-plan", one_chip)
    for mod in (pops, qops, sops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    pools = {ln: {"kv": jax.tree.map(
        lambda a: spec(one_chip, a.shape, a.dtype), c["kv"])}
        for ln, c in lm.init_paged_caches(cfg, 64, PAGE, 3072,
                                          abstract=True).items()
        if "kv" in c}
    text = jax.jit(steps.make_paged_prefill_step(cfg)).lower(
        params, {"tokens": spec(one_chip, (1, 1024), jnp.int32)}, pools,
        spec(one_chip, (1, 1024 // PAGE), jnp.int32),
        spec(one_chip, (1,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
