"""Reference implementations for the paged-attention decode kernel.

Two oracles with different jobs:

* :func:`paged_attention_ref` -- the tolerance oracle of ``kernel.py``:
  the same python loop over KV head groups, the same per-page 2-D dots,
  the same online-softmax update order (it calls the kernel's own
  :func:`~repro.kernels.paged_attention.kernel.page_update`).  The two
  agree to a few f32 ULP: whether each page's rescale-then-add is
  contracted into an FMA depends on how each graph is compiled.  It loops
  over slots and pages in python, so it is an oracle, not a fast path.

* :func:`paged_attention_view` -- the production off-TPU fallback: one
  vectorized gather of the slot's pages into the logically-ordered dense
  view followed by the exact op sequence of ``blocks.decode_attention``.
  When ``page_size`` divides ``max_len`` this is bitwise identical to
  the dense backend's attention (the PR 3 invariant), so CPU serving
  keeps dense-vs-paged token equality while TPU serving runs the
  in-place kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel as _k


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        tables: jax.Array, pos: jax.Array, *,
                        window: int = 0, chunked: bool = False,
                        cap: float = 0.0) -> jax.Array:
    """Tolerance oracle of the Pallas kernel (see module docstring).

    q: (B, H, D); k_pool/v_pool: (n, page_size, Hkv * D) lane-dense
    pages; tables: (B, P); pos: (B,).  Returns (B, H, D) in q's dtype.
    """
    b, h, d = q.shape
    page_size = k_pool.shape[1]
    n_pb = tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    outs = []
    for bi in range(b):
        qi = q[bi].astype(jnp.float32)
        posn = pos[bi]
        m = jnp.full((h, 1), _k.NEG_INF, jnp.float32)
        l = jnp.zeros((h, 1), jnp.float32)
        acc = jnp.zeros((h, d), jnp.float32)
        for p in range(n_pb):
            phys = tables[bi, p]
            page_start = p * page_size
            live = _k.page_live(phys, page_start, posn, page_size,
                                window=window, chunked=chunked)
            k = jax.lax.dynamic_index_in_dim(
                k_pool, phys, 0, keepdims=False).astype(jnp.float32)
            v = jax.lax.dynamic_index_in_dim(
                v_pool, phys, 0, keepdims=False).astype(jnp.float32)
            m2, l2, a2 = _k.page_update(qi, k, v, m, l, acc, page_start,
                                        posn, scale=scale, window=window,
                                        chunked=chunked, cap=cap)
            # dead pages leave the state untouched, exactly like the
            # kernel's pl.when skip (jnp.where also drops any NaN the
            # null page may hold)
            m = jnp.where(live, m2, m)
            l = jnp.where(live, l2, l)
            acc = jnp.where(live, a2, acc)
        outs.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
    return jnp.stack(outs)


def paged_attention_view(q: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, tables: jax.Array,
                         pos: jax.Array, *, window: int = 0,
                         chunked: bool = False, cap: float = 0.0
                         ) -> jax.Array:
    """Gathered-view fallback: pool pages -> dense (B, P * page_size)
    rows, then the dense decode-attention math.  NOTE: the op sequence
    below deliberately replicates ``blocks.decode_attention`` (repeat_kv,
    the einsum specs, -1e30 masking, jax.nn.softmax) so the result is
    bitwise identical to the dense cache backend.
    """
    b, h, d = q.shape
    hkv = k_pool.shape[2] // d
    ck = k_pool[tables].reshape(b, -1, hkv, d)
    cv = v_pool[tables].reshape(b, -1, hkv, d)
    s = ck.shape[1]
    n_rep = h // hkv
    if n_rep > 1:
        ck = jnp.broadcast_to(ck[:, :, :, None, :],
                              (b, s, hkv, n_rep, d)).reshape(b, s, h, d)
        cv = jnp.broadcast_to(cv[:, :, :, None, :],
                              (b, s, hkv, n_rep, d)).reshape(b, s, h, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q[:, None].astype(jnp.float32),
                        ck.astype(jnp.float32)) / math.sqrt(d)
    if cap > 0:
        logits = cap * jnp.tanh(logits / cap)
    pos_k = jnp.arange(s)
    pos_b = jnp.asarray(pos)                                # (B,)
    mask = pos_k[None, :] <= pos_b[:, None]                 # (B, S)
    if window > 0 and not chunked:
        mask &= pos_k[None, :] > pos_b[:, None] - window
    if window > 0 and chunked:
        mask &= (pos_k[None, :] // window) == (pos_b[:, None] // window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, cv.astype(jnp.float32))
    return out[:, 0].astype(q.dtype)
