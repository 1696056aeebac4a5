"""Pure-jnp oracle for quant_matmul (+ the bit-packing helpers)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def pack_weights(wq: np.ndarray, bits: int) -> np.ndarray:
    """Pack signed `bits`-bit integers (N, K) into int8 (N, K*bits/8) in
    the kernel's planar layout: byte ``j`` holds the values at
    ``k = j + i * K*bits/8``, value ``i`` in bits ``[bits*i, bits*(i+1))``.
    K must be a multiple of 8/bits."""
    if bits == 8:
        return wq.astype(np.int8)
    per = 8 // bits
    n, k = wq.shape
    assert k % per == 0
    u = (wq.astype(np.int32) & ((1 << bits) - 1)).astype(np.uint8)
    u = u.reshape(n, per, k // per)
    out = np.zeros((n, k // per), np.uint8)
    for i in range(per):
        out |= u[:, i, :] << (bits * i)
    return out.view(np.int8)


def quant_matmul_ref(xq: jax.Array, wq: jax.Array, sw: jax.Array,
                     sx: jax.Array) -> jax.Array:
    """xq: (M, K) int8; wq: (N, K) int8 *unpacked*; sw: (N,) f32; sx: ()."""
    acc = jnp.einsum("mk,nk->mn", xq.astype(jnp.int32),
                     wq.astype(jnp.int32))
    return acc.astype(jnp.float32) * sw[None, :] * sx


def quantize_activations(x: jax.Array):
    """Per-tensor symmetric int8 activation quantization -> (xq, sx)."""
    sx = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    return xq, sx
