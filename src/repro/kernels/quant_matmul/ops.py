"""Jitted wrapper for quant_matmul: padding, packing, backend dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant_matmul import kernel as _k
from repro.kernels.quant_matmul import ref as _ref

pack_weights = _ref.pack_weights
quantize_activations = _ref.quantize_activations


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("w_bits",))
def quant_matmul(xq: jax.Array, wq_packed: jax.Array, sw: jax.Array,
                 sx: jax.Array, w_bits: int = 8) -> jax.Array:
    """Y = (Xq @ Wq^T) * sx * sw.  xq: (M, K) int8; wq_packed:
    (N, ceil(K*w_bits/8)) int8 in the planar layout of
    :func:`pack_weights`; sw: (N,); sx: scalar. Returns (M, N) f32."""
    m, k = xq.shape
    n, kp = wq_packed.shape
    per = 8 // w_bits
    # split x into the same planes as the packed weight: plane i holds
    # columns [i*kp, (i+1)*kp) (K zero-padded up to per*kp)
    planes = _pad_to(xq, per * kp, 1).reshape(m, per, kp).transpose(1, 0, 2)
    bm = min(_k.DEFAULT_BM, max(8, m))
    bn = min(_k.DEFAULT_BN, max(128, n))
    bk = min(_k.DEFAULT_BK, max(128, kp))
    xp = _pad_to(_pad_to(planes, bm, 1), bk, 2)
    wp = _pad_to(_pad_to(wq_packed, bn, 0), bk, 1)
    swp = _pad_to(sw.reshape(1, -1), bn, 1)
    out = _k.quant_matmul_fwd(
        xp, wp, swp, sx.reshape(1, 1).astype(jnp.float32), w_bits=w_bits,
        bm=bm, bn=bn, bk=bk, interpret=not _on_tpu())
    return out[:m, :n]


def quantized_linear_apply(x: jax.Array, packed_layers) -> jax.Array:
    """Apply a reordered mixed-precision layer (paper Fig. 3): the layer is
    a list of per-precision sub-matmuls whose outputs concatenate along N.

    packed_layers: [(w_bits, wq_packed (Ni, K*bits/8), sw (Ni,)), ...]
    Delegates to ``repro.nn.quantized.mixed_precision_matmul`` (per-row
    activation scales, batch-invariant; a fully-pruned empty layer list
    yields a zero-width (M, 0) result).
    """
    from repro.nn import quantized as nnq
    return nnq.mixed_precision_matmul(x, packed_layers)
