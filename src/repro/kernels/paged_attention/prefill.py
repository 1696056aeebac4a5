"""Pallas TPU kernel: q-chunked prefill attention directly over a paged
KV pool.

Decode (``kernel.py``) reads one query token per slot against the slot's
pages.  Admission-time prefill is the other half: the WHOLE prompt's
queries attend the prompt's own keys, which the serving stack has just
scattered into :class:`~repro.serve.cache.PagedCache` pages.  Before
this kernel, prefill ran dense flash attention on a page-count-padded
copy of the prompt and a host-side ``_scatter_pages`` jit round-tripped
the dense KV into the pool afterwards.  This kernel reads the pool **in
place**:

    grid = (slot, q-chunk, page-block); the page-block axis is
    innermost, so it executes sequentially per (slot, q-chunk) and the
    online-softmax state (running max / denominator / weighted-value
    accumulator, one row per query in the chunk) lives in VMEM scratch
    across page blocks.

    The K/V block specs index the pool THROUGH the scalar-prefetched
    block table, exactly like decode: ``index_map = (tables[b, p], 0,
    0)`` over lane-dense ``(page_size, Hkv * D)`` pages.  Null (page 0)
    entries collapse consecutive dead
    iterations onto one block -- Pallas elides the re-fetch -- and
    ``pl.when`` skips their compute entirely, including every page that
    lies wholly above the q chunk (causal) or wholly below the attention
    window.

    GQA is in-kernel: one (Q*G, T) MXU dot per KV head group against the
    shared K page, KV head ``i`` being the static lane slice
    ``[:, i*D:(i+1)*D]`` -- no head-repeated materialization.

Numerics contract: identical to decode -- masked positions score
``-1e30`` and :func:`paged_prefill_ref` mirrors the kernel
operation-for-operation; the tests hold the two to a few f32 ULP, since
whether each page's rescale-then-add contracts into an FMA depends on
how each graph is compiled.  Because every output row is an independent
online softmax over its own key range, the q-chunk width changes nothing
beyond that rounding.  Padded query rows
(positions at or beyond the slot's ``lens``) produce finite garbage that
the caller discards; they never influence real rows (causality).

:func:`paged_prefill_view` is the production off-TPU fallback: one
vectorized pool gather followed by the exact op sequence of
``blocks.flash_attention``, so CPU serving keeps the dense-vs-paged
token-equality invariant while TPU serving runs the in-place kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.kernel import NEG_INF


def prefill_page_mask(page_start, qc_start, q_chunk: int, t: int, *,
                      window: int, chunked: bool):
    """(q_chunk, t) bool mask of attendable (query, key) position pairs
    for one q chunk against one page.

    ``page_start`` / ``qc_start`` may be python ints (reference path) or
    traced scalars (kernel path).  Matches ``blocks.flash_attention``'s
    causal / sliding-window / chunk-local mask formulas exactly.
    """
    pos_q = qc_start + jax.lax.broadcasted_iota(jnp.int32, (q_chunk, t), 0)
    pos_k = page_start + jax.lax.broadcasted_iota(jnp.int32, (q_chunk, t), 1)
    mask = pos_k <= pos_q
    if window > 0 and not chunked:
        mask &= pos_k > pos_q - window
    if window > 0 and chunked:
        mask &= (pos_k // window) == (pos_q // window)
    return mask


def prefill_page_live(phys, page_start, page_size: int, qc_start,
                      qc_end, *, window: int, chunked: bool):
    """Whether a page contributes to a q chunk at all: physically backed
    (non-null) AND not wholly above the chunk's last query (causal) AND
    not wholly below the chunk's attention window."""
    live = jnp.logical_and(phys != 0, page_start <= qc_end)
    page_end = page_start + page_size - 1
    if window > 0 and not chunked:
        live = jnp.logical_and(live, page_end > qc_start - window)
    if window > 0 and chunked:
        live = jnp.logical_and(live,
                               page_end >= (qc_start // window) * window)
    return live


def prefill_page_update(q, k, v, m, l, acc, page_start, qc_start, *,
                        scale: float, window: int, chunked: bool,
                        cap: float):
    """One page's online-softmax contribution for one q chunk.  Shared by
    the kernel body and :func:`paged_prefill_ref` so the two compute the
    same math.

    q: (Q, H, D) f32; k/v: (T, Hkv * D) f32 lane-dense pages; m/l:
    (Q, H, 1) f32 running max/denominator; acc: (Q, H, D) f32.  Returns
    updated (m, l, acc).
    """
    qc, h, d = q.shape
    t = k.shape[0]
    hkv = k.shape[1] // d
    g = h // hkv
    rows = []
    for i in range(hkv):
        qg = q[:, i * g:(i + 1) * g, :].reshape(qc * g, d)
        rows.append(jax.lax.dot_general(
            qg, k[:, i * d:(i + 1) * d], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32
        ).reshape(qc, g, t))                               # (Q, G, T)
    s = jnp.concatenate(rows, axis=1) * scale              # (Q, H, T)
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    mask = prefill_page_mask(page_start, qc_start, qc, t, window=window,
                             chunked=chunked)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    outs = []
    for i in range(hkv):
        pg = p[:, i * g:(i + 1) * g, :].reshape(qc * g, t)
        outs.append(jax.lax.dot_general(
            pg, v[:, i * d:(i + 1) * d], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        ).reshape(qc, g, d))                               # (Q, G, D)
    acc_new = acc * corr + jnp.concatenate(outs, axis=1)
    return m_new, l_new, acc_new


def _paged_prefill_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref,
                          out_ref, m_ref, l_ref, acc_ref, *,
                          page_size: int, q_chunk: int, n_pb: int,
                          scale: float, window: int, chunked: bool,
                          cap: float):
    del lens_ref  # masking is purely positional; lens rides along so the
    #               engine's jit signature stays static across prompts
    b = pl.program_id(0)
    qc = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    phys = tables_ref[b, p]
    page_start = p * page_size
    qc_start = qc * q_chunk
    qc_end = qc_start + q_chunk - 1
    live = prefill_page_live(phys, page_start, page_size, qc_start,
                             qc_end, window=window, chunked=chunked)

    @pl.when(live)
    def _compute():
        m_new, l_new, acc_new = prefill_page_update(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), m_ref[...], l_ref[...],
            acc_ref[...], page_start, qc_start, scale=scale,
            window=window, chunked=chunked, cap=cap)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

    @pl.when(p == n_pb - 1)
    def _epilogue():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def paged_prefill_fwd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      tables: jax.Array, lens: jax.Array, *,
                      window: int = 0, chunked: bool = False,
                      cap: float = 0.0, q_chunk: int = 16,
                      interpret: bool = True) -> jax.Array:
    """q: (B, S, H, D) with S a multiple of ``q_chunk`` (the caller pads;
    padded rows produce discarded garbage); k_pool/v_pool: (n,
    page_size, Hkv * D) lane-dense pages with page 0 the reserved null
    page; tables: (B, P) int32 page ids (0 = unbacked); lens: (B,) int32
    real prompt lengths.  Returns (B, S, H, D) in q's dtype.
    """
    b, s, h, d = q.shape
    page_size, width = k_pool.shape[1], k_pool.shape[2]
    hkv = width // d
    n_pb = tables.shape[1]
    q_chunk = min(q_chunk, s)
    assert s % q_chunk == 0, (s, q_chunk)
    assert width == hkv * d and h % hkv == 0, (h, width, d)
    n_qc = s // q_chunk
    scale = 1.0 / math.sqrt(d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_qc, n_pb),
        in_specs=[
            pl.BlockSpec((1, q_chunk, h, d),
                         lambda bb, qc, p, tbl, ln: (bb, qc, 0, 0)),
            pl.BlockSpec((1, page_size, width),
                         lambda bb, qc, p, tbl, ln: (tbl[bb, p], 0, 0)),
            pl.BlockSpec((1, page_size, width),
                         lambda bb, qc, p, tbl, ln: (tbl[bb, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_chunk, h, d),
                               lambda bb, qc, p, tbl, ln: (bb, qc, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_chunk, h, 1), jnp.float32),   # running max
            pltpu.VMEM((q_chunk, h, 1), jnp.float32),   # running denom
            pltpu.VMEM((q_chunk, h, d), jnp.float32),   # weighted-V acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_prefill_kernel, page_size=page_size,
                          q_chunk=q_chunk, n_pb=n_pb, scale=scale,
                          window=window, chunked=chunked, cap=cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, h, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q, k_pool, v_pool)


def paged_prefill_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      tables: jax.Array, lens: jax.Array, *,
                      window: int = 0, chunked: bool = False,
                      cap: float = 0.0, q_chunk: int = 16) -> jax.Array:
    """Tolerance oracle of the Pallas prefill kernel: the same python
    loop over KV head groups, the same per-(q-chunk, page) 2-D dots, the
    same online-softmax update order (it calls the kernel's own
    :func:`prefill_page_update`).  Slots and q chunks unroll in python;
    the page axis is a ``lax.fori_loop`` whose carried state mirrors the
    kernel's VMEM scratch and whose ``lax.cond`` mirrors the ``pl.when``
    dead-page skip.  It agrees with the kernel to a few f32 ULP.  An
    oracle, not a fast path."""
    b, s, h, d = q.shape
    page_size = k_pool.shape[1]
    n_pb = tables.shape[1]
    q_chunk = min(q_chunk, s)
    assert s % q_chunk == 0, (s, q_chunk)
    scale = 1.0 / math.sqrt(d)
    del lens  # masking is purely positional, exactly like the kernel
    outs = []
    for bi in range(b):
        chunks = []
        for ci in range(s // q_chunk):
            qc_start = ci * q_chunk
            qc_end = qc_start + q_chunk - 1
            qi = q[bi, qc_start:qc_start + q_chunk].astype(jnp.float32)
            m = jnp.full((q_chunk, h, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((q_chunk, h, 1), jnp.float32)
            acc = jnp.zeros((q_chunk, h, d), jnp.float32)

            def page_body(p, state, qi=qi, bi=bi, qc_start=qc_start,
                          qc_end=qc_end):
                m, l, acc = state
                phys = tables[bi, p]
                page_start = p * page_size
                live = prefill_page_live(phys, page_start, page_size,
                                         qc_start, qc_end, window=window,
                                         chunked=chunked)
                k = jax.lax.dynamic_index_in_dim(
                    k_pool, phys, 0, keepdims=False).astype(jnp.float32)
                v = jax.lax.dynamic_index_in_dim(
                    v_pool, phys, 0, keepdims=False).astype(jnp.float32)
                # dead pages leave the state untouched and run no
                # arithmetic at all, exactly like pl.when (any NaN the
                # null page may hold never enters the taken branch)
                return jax.lax.cond(
                    live,
                    lambda st: prefill_page_update(
                        qi, k, v, *st, page_start, qc_start, scale=scale,
                        window=window, chunked=chunked, cap=cap),
                    lambda st: st,
                    (m, l, acc))

            m, l, acc = jax.lax.fori_loop(0, n_pb, page_body,
                                          (m, l, acc))
            chunks.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
        outs.append(jnp.concatenate(chunks, axis=0))
    return jnp.stack(outs)


def paged_prefill_view(q: jax.Array, k_pool: jax.Array,
                       v_pool: jax.Array, tables: jax.Array,
                       lens: jax.Array, *, window: int = 0,
                       chunked: bool = False, cap: float = 0.0
                       ) -> jax.Array:
    """Gathered-view fallback: pool pages -> dense (B, P * page_size)
    KV rows, then the dense flash-attention math.  NOTE: the op sequence
    below deliberately replicates ``blocks.flash_attention`` (repeat_kv,
    the per-q-chunk static kv ranges, the kv lax.scan with the carried
    chunk counter, the einsum specs, -1e30 masking) so real query rows
    are bitwise identical to the dense cache backend's prefill -- the
    extra masked tail keys score -1e30 and contribute exact zeros.
    ``blocks`` cannot be imported here (it imports this package), hence
    the inline replica.
    """
    b, s, h, d = q.shape
    hkv = k_pool.shape[2] // d
    k = k_pool[tables].reshape(b, -1, hkv, d)
    v = v_pool[tables].reshape(b, -1, hkv, d)
    del lens  # real rows self-select via the causal mask
    skv = k.shape[1]
    n_rep = h // hkv
    if n_rep > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, skv, hkv, n_rep, d)).reshape(b, skv, h, d)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, skv, hkv, n_rep, d)).reshape(b, skv, h, d)
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(1024, s)
    kv_chunk = min(1024, skv)
    assert s % q_chunk == 0 and skv % kv_chunk == 0

    outs = []
    for i in range(s // q_chunk):
        q0 = i * q_chunk
        qi = q[:, q0:q0 + q_chunk]                       # (B, Q, H, D)
        pos_q = q0 + jnp.arange(q_chunk)
        hi = min(q0 + q_chunk, skv)
        lo = 0
        if window > 0:
            lo = max(0, q0 - (window - 1)) if not chunked \
                else (q0 // window) * window
        lo = (lo // kv_chunk) * kv_chunk
        hi_pad = -(-hi // kv_chunk) * kv_chunk
        hi_pad = min(hi_pad, skv)
        n_kv = max((hi_pad - lo) // kv_chunk, 1)
        ks = jax.lax.dynamic_slice_in_dim(k, lo, n_kv * kv_chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, n_kv * kv_chunk, 1)
        ks = ks.reshape(b, n_kv, kv_chunk, h, d).transpose(1, 0, 2, 3, 4)
        vs = vs.reshape(b, n_kv, kv_chunk, h, d).transpose(1, 0, 2, 3, 4)

        def body(carry, inp):
            m, l, acc, j = carry
            kj, vj = inp
            p0 = lo + j * kv_chunk
            pos_k = p0 + jnp.arange(kv_chunk)
            sij = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32),
                             kj.astype(jnp.float32)) * scale
            if cap > 0:
                sij = cap * jnp.tanh(sij / cap)
            mask = pos_k[None, :] <= pos_q[:, None]
            if window > 0 and not chunked:
                mask &= pos_k[None, :] > pos_q[:, None] - window
            if window > 0 and chunked:
                mask &= (pos_k[None, :] // window) == \
                    (pos_q[:, None] // window)
            sij = jnp.where(mask[None, None], sij, -1e30)
            m_new = jnp.maximum(m, jnp.max(sij, axis=-1))
            p = jnp.exp(sij - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new, j + 1), None

        m0 = jnp.full((b, h, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            body, (m0, l0, a0, jnp.asarray(0, jnp.int32)), (ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(out.transpose(0, 2, 1, 3).astype(q.dtype))
    return jnp.concatenate(outs, axis=1)                 # (B, S, H, D)
