"""Pallas TPU kernel: decode attention directly over a paged KV pool.

The serving stack's :class:`~repro.serve.cache.PagedCache` stores KV in a
fixed pool of ``page_size``-token pages plus per-slot block tables of
physical page ids.  Before this kernel, every decode step gathered the
slot's pages into a dense ``(B, max_len, Hkv, D)`` view and ran ordinary
masked attention over it -- strictly more memory traffic than dense
decode, and every never-written position was still scanned.  This kernel
reads the pool **in place**:

    grid = (slot, page-block); the page-block axis is innermost, so it
    executes sequentially per slot and the online-softmax state (running
    max / denominator / weighted-value accumulator) lives in VMEM scratch
    across page blocks.

    The K/V block specs index the pool THROUGH the scalar-prefetched
    block table: ``index_map = (tables[b, p], 0, 0)``.  Pages are
    lane-dense, ``(page_size, Hkv * D)``: a TPU stores such a pool
    row-major with no padding, so the kernel reads the pool the program
    holds, with no relayout copy.  Entries beyond
    a slot's live length are 0 (the reserved null page), so consecutive
    dead iterations map to the same physical block and Pallas elides the
    re-fetch; ``pl.when`` skips their compute entirely.  HBM traffic per
    step is therefore proportional to the tokens actually held, not to
    ``max_batch * max_len``.

    GQA is handled in-kernel (one 2-D MXU dot per KV head group against
    the shared K page, KV head ``i`` being the static lane slice
    ``[:, i*D:(i+1)*D]``) -- no head-repeated cache materialization.

Numerics contract: masked positions score ``-1e30`` exactly like the
dense ``blocks.decode_attention`` path; a slot whose table row is all
null (inactive / freed mid-batch) produces a finite all-zero output (the
denominator is clamped).  ``ref.paged_attention_ref`` mirrors this
kernel's math operation-for-operation (same per-page 2-D dots, same
online-softmax update order); the kernel tests hold the two to a few
f32 ULP, since whether each page's rescale-then-add contracts into an
FMA depends on how each graph is compiled.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def page_mask(page_start, posn: jax.Array, t: int, *, window: int,
              chunked: bool):
    """(1, t) bool mask of attendable positions inside one page.

    ``page_start`` may be a python int (reference path) or a traced
    scalar (kernel path); ``posn`` is the slot's current decode position
    (the newest written token, always attendable).
    """
    pos_k = page_start + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    mask = pos_k <= posn
    if window > 0 and not chunked:
        mask &= pos_k > posn - window
    if window > 0 and chunked:
        mask &= (pos_k // window) == (posn // window)
    return mask


def page_live(phys, page_start, posn: jax.Array, page_size: int, *,
              window: int, chunked: bool):
    """Whether a page contributes at all: physically backed (non-null)
    AND not wholly beyond the slot's live length AND not wholly below the
    attention window."""
    live = jnp.logical_and(phys != 0, page_start <= posn)
    page_end = page_start + page_size - 1
    if window > 0 and not chunked:
        live = jnp.logical_and(live, page_end > posn - window)
    if window > 0 and chunked:
        live = jnp.logical_and(live, page_end >= (posn // window) * window)
    return live


def page_update(q, k, v, m, l, acc, page_start, posn, *, scale: float,
                window: int, chunked: bool, cap: float):
    """One page's online-softmax contribution.  Shared by the kernel body
    and :func:`ref.paged_attention_ref` so the two compute the same math.

    q: (H, D) f32; k/v: (T, Hkv * D) f32 lane-dense pages; m/l: (H, 1)
    f32 running max/denominator; acc: (H, D) f32.  Returns updated
    (m, l, acc).
    """
    h, d = q.shape
    t = k.shape[0]
    hkv = k.shape[1] // d
    g = h // hkv
    rows = []
    for i in range(hkv):
        rows.append(jax.lax.dot_general(
            q[i * g:(i + 1) * g], k[:, i * d:(i + 1) * d],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))       # (G, T)
    s = jnp.concatenate(rows, axis=0) * scale          # (H, T)
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    mask = page_mask(page_start, posn, t, window=window, chunked=chunked)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    outs = []
    for i in range(hkv):
        outs.append(jax.lax.dot_general(
            p[i * g:(i + 1) * g], v[:, i * d:(i + 1) * d],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))       # (G, D)
    acc_new = acc * corr + jnp.concatenate(outs, axis=0)
    return m_new, l_new, acc_new


def _paged_attn_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
                       m_ref, l_ref, acc_ref, *, page_size: int, n_pb: int,
                       scale: float, window: int, chunked: bool,
                       cap: float):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    phys = tables_ref[b, p]
    posn = pos_ref[b]
    page_start = p * page_size
    live = page_live(phys, page_start, posn, page_size, window=window,
                     chunked=chunked)

    @pl.when(live)
    def _compute():
        m_new, l_new, acc_new = page_update(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), m_ref[...], l_ref[...],
            acc_ref[...], page_start, posn, scale=scale, window=window,
            chunked=chunked, cap=cap)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

    @pl.when(p == n_pb - 1)
    def _epilogue():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def paged_attention_fwd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        tables: jax.Array, pos: jax.Array, *,
                        window: int = 0, chunked: bool = False,
                        cap: float = 0.0, interpret: bool = True
                        ) -> jax.Array:
    """q: (B, H, D); k_pool/v_pool: (n, page_size, Hkv * D) lane-dense
    pages with page 0 the reserved null page; tables: (B, P) int32 page
    ids (0 = unbacked); pos: (B,) int32 per-slot decode positions.
    Returns (B, H, D) in q's dtype.
    """
    b, h, d = q.shape
    page_size, width = k_pool.shape[1], k_pool.shape[2]
    hkv = width // d
    n_pb = tables.shape[1]
    assert width == hkv * d and h % hkv == 0, (h, width, d)
    scale = 1.0 / math.sqrt(d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pb),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bb, p, tbl, ps: (bb, 0, 0)),
            pl.BlockSpec((1, page_size, width),
                         lambda bb, p, tbl, ps: (tbl[bb, p], 0, 0)),
            pl.BlockSpec((1, page_size, width),
                         lambda bb, p, tbl, ps: (tbl[bb, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bb, p, tbl, ps: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),       # running max
            pltpu.VMEM((h, 1), jnp.float32),       # running denominator
            pltpu.VMEM((h, d), jnp.float32),       # weighted-V accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size,
                          n_pb=n_pb, scale=scale, window=window,
                          chunked=chunked, cap=cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), q, k_pool, v_pool)
