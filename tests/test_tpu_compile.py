"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode runs the kernels' math on the CPU but not Mosaic's
lowering, which refuses what interpret mode accepts (an
``optimization_barrier``, a vector shape cast it cannot lay out, a block
that is not tile-aligned, too much VMEM).  These tests compile each
kernel at real serving widths for a v5e that is described, not
attached, and check that the program holds the kernel
(``tpu_custom_call``).  Nothing runs, so nothing here says anything about
results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import kernel as pk
from repro.kernels.paged_attention import prefill as pf
from repro.kernels.quant_matmul import kernel as qk

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
    pool = spec(one_chip, (N_PAGES + 1, PAGE, hkv, d), dt)
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
    pool = spec(one_chip, (N_PAGES + 1, PAGE, hkv, d), dt)
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
