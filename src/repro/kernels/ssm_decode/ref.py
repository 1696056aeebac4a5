"""Pure-jnp oracle for the ssm_decode_step kernel: the three einsums the
decode branch of ``nn.blocks.mamba2_layer`` computes off the TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssm_decode_ref(state: jax.Array, x: jax.Array, dt: jax.Array,
                   a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array):
    """Shapes as ``kernel.ssm_decode_step_fwd``.  Returns (y, state')."""
    dec = jnp.exp(dt * a)                                     # (B, H)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt, x, b)
    s_new = dec[..., None, None] * state + upd
    y = jnp.einsum("bhpn,bn->bhp", s_new, c)
    y = y + d.astype(jnp.float32)[None, :, None] * x
    return y, s_new
