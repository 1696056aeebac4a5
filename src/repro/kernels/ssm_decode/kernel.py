"""Pallas TPU kernel: one decode step of the Mamba-2 recurrence.

For every row ``b`` and head ``h`` of a decode batch, with the state
``s`` (P, N), the head's input ``x`` (P,), step ``dt``, decay rate ``A``
(negative), skip ``D`` and the row's ``B``, ``C`` (N,):

    s' = exp(dt * A) * s + (dt * x) B^T
    y  = s' C + D * x

The state is f32 (B, H, P, N) and is updated in place
(``input_output_aliases``): each block is read once and written once,
and the output ``y`` is produced from the block while it is in VMEM.
The state is the step's traffic (4 B per value each way); everything
else is O(B * H * P).

Grid: (B, H / BH), one row's BH heads a step.  The per-head scalars ride
as (..., 1) columns and B, C as (B, 1, N) rows, so every operand's
block ends in a whole minor dimension.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NAME = "ssm_decode_step"
DEFAULT_BH = 32


def _kernel(s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
            so_ref):
    x = x_ref[0]                                   # (BH, P)
    dt = dt_ref[0]                                 # (BH, 1)
    dec = jnp.exp(dt * a_ref[...])                 # (BH, 1)
    u = (dt * x)[:, :, None]                       # (BH, P, 1)
    s = dec[:, :, None] * s_ref[0] + u * b_ref[0][None]   # (BH, P, N)
    so_ref[0] = s
    y = jnp.sum(s * c_ref[0][None], axis=-1)       # (BH, P)
    y_ref[0] = y + d_ref[...] * x


def ssm_decode_step_fwd(state: jax.Array, x: jax.Array, dt: jax.Array,
                        a: jax.Array, b: jax.Array, c: jax.Array,
                        d: jax.Array, *, bh: int = DEFAULT_BH,
                        interpret: bool = False):
    """state: (B, H, P, N) f32; x: (B, H, P) f32; dt: (B, H) f32;
    a, d: (H,) f32; b, c: (B, N) f32.  Returns (y (B, H, P) f32, the new
    state, which is ``state``'s buffer)."""
    nb, h, p, n = state.shape
    bh = bh if h % bh == 0 else h
    col = lambda v: v.astype(jnp.float32)[..., None]   # noqa: E731
    row = lambda v: v.astype(jnp.float32)[:, None, :]  # noqa: E731
    return pl.pallas_call(
        _kernel,
        grid=(nb, h // bh),
        in_specs=[
            pl.BlockSpec((1, bh, p, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bh, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bh, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bh, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bh, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bh, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, h, p), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        input_output_aliases={0: 1},
        interpret=interpret,
        name=NAME,
    )(state, x.astype(jnp.float32), col(dt), col(a), row(b), row(c),
      col(d))
