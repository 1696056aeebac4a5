"""Pallas TPU kernel: int8 x int8 tiled matmul with fused per-channel dequant.

Serving path for the discretized models (paper Sec. 4.5 / Fig. 3): after
channel reordering, each layer is a set of dense per-precision sub-matmuls.
Sub-8-bit weights are stored bit-packed in int8 words and unpacked in-kernel
(bandwidth win; the MXU computes at int8 regardless -- see DESIGN.md
"hardware adaptation").

Y[m, n] = (sum_k Xq[m, k] * Wq[n, k]) * sx * sw[n]

Packed layout is *planar*: with ``per = 8 / bits`` values per byte and
``Kp = K / per`` bytes per row, byte ``j`` of a row holds the values at
``k = j + i * Kp`` for ``i = 0 .. per - 1``, value ``i`` in bits
``[bits * i, bits * (i + 1))``.  Unpacking plane ``i`` is then a shift and
sign-extension of the whole ``(BN, BKp)`` block -- no interleaving
reshape, which Mosaic cannot lower -- and the wrapper hands the kernel
``x`` split the same way, as ``(per, M, Kp)``, so each plane is one 2-D
dot against its own activation slice.

Grid: (M/BM, N/BN, Kp/BKp); the packed-K axis is the innermost (sequential)
axis, accumulated in an f32 VMEM scratch-free accumulator held in the output
block (int32 partials fit f32 exactly: 127*127*BK < 2^24 for w8 with
BK <= 1024, and the narrower widths' smaller values cover their wider K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512     # packed bytes per row per grid step


def _qmm_kernel(x_ref, w_ref, sw_ref, sx_ref, out_ref, *, nk: int,
                w_bits: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    planes = _unpack(w_ref[...], w_bits)          # per x (BN, BKp) int32
    acc = out_ref[...]
    for i, w in enumerate(planes):
        x = x_ref[i].astype(jnp.float32)          # (BM, BKp)
        acc += jax.lax.dot_general(
            x, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    out_ref[...] = acc

    @pl.when(k == nk - 1)
    def _epilogue():
        sw = sw_ref[...]                          # (1, BN)
        sx = sx_ref[0, 0]
        out_ref[...] = out_ref[...] * sw * sx


def _unpack(w: jax.Array, bits: int) -> list:
    """Planar-packed int8 words (N, Kp) -> ``8 // bits`` signed int32
    planes (N, Kp); plane ``i`` holds the values at ``k = j + i * Kp``.
    Each field is shifted to the top of the int32 word and shifted back
    arithmetically, which sign-extends it."""
    w = w.astype(jnp.int32)
    if bits == 8:
        return [w]
    return [(w << (32 - bits * (i + 1))) >> (32 - bits)
            for i in range(8 // bits)]


def quant_matmul_fwd(x_planes: jax.Array, wq_packed: jax.Array,
                     sw: jax.Array, sx: jax.Array, *, w_bits: int = 8,
                     bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                     bk: int = DEFAULT_BK, interpret: bool = True
                     ) -> jax.Array:
    """x_planes: (8/bits, M, Kp) int8, plane ``i`` holding columns
    ``[i * Kp, (i + 1) * Kp)`` of the activations; wq_packed: (N, Kp) int8
    in the planar layout; sw: (1, N) f32; sx: (1, 1) f32.  ``bk`` is the
    packed-K block.  Shapes must already be tile-aligned."""
    per, m, kp = x_planes.shape
    n = wq_packed.shape[0]
    assert per == 8 // w_bits and wq_packed.shape[1] == kp, (
        x_planes.shape, wq_packed.shape, w_bits)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kp)
    nk = kp // bk
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, nk=nk, w_bits=w_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((per, bm, bk), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x_planes, wq_packed, sw, sx)
