"""Backend dispatch for the Mamba-2 decode-state step."""
from __future__ import annotations

import jax

from repro.kernels.ssm_decode import kernel as _k
from repro.kernels.ssm_decode import ref as _ref

ssm_decode_ref = _ref.ssm_decode_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssm_decode_step(state, x, dt, a, b, c, d, use_kernel: bool | None = None):
    """One decode step of every row's recurrent state; see kernel.py for
    the equations and shapes.  Returns (y (B, H, P) f32, new state).

    ``use_kernel=None`` -> the Pallas kernel on TPU (state updated in
    place), the jnp reference elsewhere (tests run the kernel in
    interpret mode explicitly)."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return _ref.ssm_decode_ref(state, x, dt, a, b, c, d)
    return _k.ssm_decode_step_fwd(state, x, dt, a, b, c, d,
                                  interpret=not _on_tpu())
