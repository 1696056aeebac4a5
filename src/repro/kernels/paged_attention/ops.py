"""Dispatch wrapper for paged attention (decode AND prefill): kernel on
TPU, gathered view off-TPU, tolerance-oracle reference for tests.

``impl`` resolution (also overridable process-wide via :func:`force_impl`
for tests; the override pins BOTH entry points):

* ``"kernel"`` -- the Pallas kernels.  The default on TPU, where they
  are always compiled; forced elsewhere (tests), they run in interpret
  mode.
* ``"view"``   -- the gathered dense view + the dense attention op
  sequence (``decode_attention`` for decode, ``flash_attention`` for
  prefill); bitwise identical to the dense cache backend, and the fast
  formulation for CPU/GPU where the pool gather compiles to one fused
  XLA op.
* ``"ref"``    -- the kernels' math in plain jnp, python-looped; equal
  to the kernels within a few f32 ULP (oracles only).
"""
from __future__ import annotations

import contextlib
import math

import jax

from repro.kernels.paged_attention import kernel as _k
from repro.kernels.paged_attention import prefill as _pf
from repro.kernels.paged_attention import ref as _ref

paged_attention_ref = _ref.paged_attention_ref
paged_attention_view = _ref.paged_attention_view
paged_prefill_ref = _pf.paged_prefill_ref
paged_prefill_view = _pf.paged_prefill_view

# widest q chunk the prefill kernel tiles with; the actual chunk is the
# largest power-of-two divisor of the (padded) prompt length up to this
PREFILL_Q = 16

_IMPLS = ("kernel", "view", "ref")
_impl_override: str | None = None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str | None = None) -> str:
    if impl is None:
        impl = _impl_override
    if impl is None:
        impl = "kernel" if _on_tpu() else "view"
    if impl not in _IMPLS:
        raise ValueError(f"unknown paged-attention impl {impl!r} "
                         f"(expected one of {_IMPLS})")
    return impl


@contextlib.contextmanager
def force_impl(impl: str | None):
    """Test hook: pin the implementation for every call in the block."""
    global _impl_override
    prev = _impl_override
    _impl_override = resolve_impl(impl) if impl is not None else None
    try:
        yield
    finally:
        _impl_override = prev


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, pos: jax.Array, *, window: int = 0,
                    chunked: bool = False, cap: float = 0.0,
                    impl: str | None = None) -> jax.Array:
    """Decode attention over the page pool.  q: (B, H, D);
    k_pool/v_pool: (n, page_size, Hkv * D) lane-dense pages; tables:
    (B, P) page ids (0 = null); pos: (B,) per-slot positions.
    Returns (B, H, D) in q's dtype."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return _ref.paged_attention_ref(q, k_pool, v_pool, tables, pos,
                                        window=window, chunked=chunked,
                                        cap=cap)
    if impl == "view":
        return _ref.paged_attention_view(q, k_pool, v_pool, tables, pos,
                                         window=window, chunked=chunked,
                                         cap=cap)
    return _k.paged_attention_fwd(q, k_pool, v_pool, tables, pos,
                                  window=window, chunked=chunked, cap=cap,
                                  interpret=not _on_tpu())


def prefill_q_chunk(s: int) -> int:
    """Largest power-of-two q-chunk width up to :data:`PREFILL_Q` that
    tiles a length-``s`` prompt (the engine pads paged attention-only
    prompts to a multiple of PREFILL_Q, so serving always gets the full
    width; exact-length hybrid prefill degrades gracefully)."""
    return math.gcd(s, PREFILL_Q)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, tables: jax.Array,
                            lens: jax.Array, *, window: int = 0,
                            chunked: bool = False, cap: float = 0.0,
                            impl: str | None = None) -> jax.Array:
    """Prefill attention over the page pool.  q: (B, S, H, D) -- the
    prompt's queries, rows at or beyond ``lens`` being discarded
    padding; k_pool/v_pool: (n, page_size, Hkv * D) lane-dense pages;
    tables: (B, P) page ids (0 = null); lens: (B,) real prompt
    lengths.  Returns (B, S, H, D) in q's dtype."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return _pf.paged_prefill_ref(q, k_pool, v_pool, tables, lens,
                                     window=window, chunked=chunked,
                                     cap=cap,
                                     q_chunk=prefill_q_chunk(q.shape[1]))
    if impl == "view":
        return _pf.paged_prefill_view(q, k_pool, v_pool, tables, lens,
                                      window=window, chunked=chunked,
                                      cap=cap)
    return _pf.paged_prefill_fwd(q, k_pool, v_pool, tables, lens,
                                 window=window, chunked=chunked, cap=cap,
                                 q_chunk=prefill_q_chunk(q.shape[1]),
                                 interpret=not _on_tpu())
