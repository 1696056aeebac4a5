"""Transformer / SSM building blocks for the assigned LM architectures.

All functions are pure; parameters are nested dicts. Sharding is expressed
through repro.distributed.sharding logical-axis constraints so the same code
runs on 1 CPU device (constraints no-op) and on the 512-chip mesh.

Implemented here:
  * RMSNorm, RoPE
  * flash attention (online-softmax, q-chunked python loop + kv lax.scan):
    causal, bidirectional, sliding-window (gemma2), chunked (llama4),
    logit softcap (gemma2), GQA, qk-norm (qwen3)
  * decode attention against a KV cache (seq-shardable)
  * SwiGLU FFN
  * top-k MoE, capacity-based token dropping or dropless, over all of
    the layer's experts or the share it holds, expert-parallel via
    shard_map over the 'model' axis (TP-style: activations replicated over
    'model', each shard computes its experts, one psum)
  * Mamba-2 SSD mixer (chunked dual form; inter-chunk pass via
    repro.kernels.ssd_scan; the decode step's state update via
    repro.kernels.ssm_decode)
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.ssm_decode import ops as ssm_ops
from repro.nn import quantized as nnq

# ---------------------------------------------------------------------------
# linear application (dense or plan-quantized)
# ---------------------------------------------------------------------------


def linear(x: jax.Array, w) -> jax.Array:
    """y[..., n] = x[..., k] @ w[k, n].

    ``w`` is either a dense array (the training / float-serving path) or a
    :class:`repro.nn.quantized.PackedLinear` -- the plan-quantized serving
    path, where the weight provider hands back bit-packed per-precision
    groups that are served through ``mixed_precision_matmul``.
    """
    if isinstance(w, nnq.PackedLinear):
        return w(x)
    return jnp.einsum("bsd,dk->bsk", x, w)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); pos: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = pos[..., None].astype(jnp.float32) * freqs       # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                       # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


# ---------------------------------------------------------------------------
# flash attention (train / prefill)
# ---------------------------------------------------------------------------


def _repeat_kv(kv: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    chunked: bool = False, cap: float = 0.0,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    q_offset: int = 0) -> jax.Array:
    """Online-softmax attention. q: (B, S, H, D); k/v: (B, Skv, Hkv, D).

    window > 0 & not chunked -> sliding-window (pos_k > pos_q - window)
    window > 0 & chunked     -> block-local (llama4 iRoPE chunks)
    Python loop over q chunks (static trip counts: the causal kv range per
    q chunk is known at trace time -> no wasted FLOPs on masked-out chunks),
    lax.scan over kv chunks (HLO stays small).
    """
    b, s, h, d = q.shape
    skv = k.shape[1]
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, skv)
    assert s % q_chunk == 0 and skv % kv_chunk == 0

    outs = []
    for i in range(s // q_chunk):
        q0 = i * q_chunk
        qi = q[:, q0:q0 + q_chunk]                       # (B, Q, H, D)
        pos_q = q_offset + q0 + jnp.arange(q_chunk)
        # static kv range for this q chunk
        hi = min(q_offset + q0 + q_chunk, skv) if causal else skv
        lo = 0
        if window > 0:
            lo = max(0, (q_offset + q0) - (window - 1)) if not chunked \
                else ((q_offset + q0) // window) * window
        lo = (lo // kv_chunk) * kv_chunk
        hi_pad = -(-hi // kv_chunk) * kv_chunk
        hi_pad = min(hi_pad, skv)
        n_kv = max((hi_pad - lo) // kv_chunk, 1)
        ks = jax.lax.dynamic_slice_in_dim(k, lo, n_kv * kv_chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, n_kv * kv_chunk, 1)
        ks = ks.reshape(b, n_kv, kv_chunk, h, d).transpose(1, 0, 2, 3, 4)
        vs = vs.reshape(b, n_kv, kv_chunk, h, d).transpose(1, 0, 2, 3, 4)

        def body(carry, inp):
            # NOTE: the kv-chunk start position is derived from the carried
            # counter j -- if it were a constant scan input, XLA would
            # constant-fold + hoist the masks of ALL chunks into one giant
            # pred[n_kv, B, H, Q, K] buffer (hundreds of MB per layer).
            m, l, acc, j = carry
            kj, vj = inp
            p0 = lo + j * kv_chunk
            pos_k = p0 + jnp.arange(kv_chunk)
            sij = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32),
                             kj.astype(jnp.float32)) * scale
            sij = softcap(sij, cap)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= pos_k[None, :] <= pos_q[:, None]
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


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, *, window: int = 0,
                     chunked: bool = False, cap: float = 0.0) -> jax.Array:
    """One-token attention. q: (B, 1, H, D); cache: (B, S, Hkv, D);
    pos: () shared index of the current token, or (B,) per-sequence indices
    (continuous batching: each slot decodes at its own position)."""
    b, s, hkv, d = cache_k.shape
    h = q.shape[2]
    k = _repeat_kv(cache_k, h // hkv)
    v = _repeat_kv(cache_v, h // hkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    logits = softcap(logits, cap)
    pos_k = jnp.arange(s)
    posv = jnp.asarray(pos)
    pos_b = posv[None] if posv.ndim == 0 else posv          # (1,) or (B,)
    mask = pos_k[None, :] <= pos_b[:, None]                 # (1|B, S)
    if window > 0 and not chunked:
        mask &= pos_k[None, :] > pos_b[:, None] - window
    if window > 0 and chunked:
        mask &= (pos_k[None, :] // window) == (pos_b[:, None] // window)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           pos: jax.Array, *, window: int = 0,
                           chunked: bool = False, cap: float = 0.0
                           ) -> jax.Array:
    """One-token attention straight over the KV page pool (no dense
    gather).  q: (B, 1, H, D); k_pool/v_pool: (n, page_size, Hkv * D)
    lane-dense pages; tables: (B, P) page ids (0 = reserved null page);
    pos: (B,) per-slot positions.  Dispatches to the Pallas kernel on
    TPU and to the gathered-view reference (bitwise identical to
    :func:`decode_attention` over the dense row) off-TPU."""
    out = paged_ops.paged_attention(q[:, 0], k_pool, v_pool, tables, pos,
                                    window=window, chunked=chunked,
                                    cap=cap)
    return out[:, None]


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, tables: jax.Array,
                            lens: jax.Array, *, window: int = 0,
                            chunked: bool = False, cap: float = 0.0
                            ) -> jax.Array:
    """Prompt attention straight over the KV page pool (no dense
    round-trip).  q: (B, S, H, D) with rows at or beyond ``lens``
    being discarded padding; k_pool/v_pool: (n, page_size, Hkv * D)
    lane-dense pages; tables: (B, P) page ids (0 = reserved null page);
    lens: (B,) real prompt lengths.  Dispatches to the
    q-chunked Pallas kernel on TPU and to the gathered-view reference
    (the dense :func:`flash_attention` op sequence) off-TPU."""
    return paged_ops.paged_prefill_attention(q, k_pool, v_pool, tables,
                                             lens, window=window,
                                             chunked=chunked, cap=cap)


# ---------------------------------------------------------------------------
# attention layer (projections + cache plumbing)
# ---------------------------------------------------------------------------


def attention_layer(p: dict, x: jax.Array, cfg, *, kind: str = "full",
                    mode: str = "train", cache=None, pos=None,
                    kv_input: Optional[jax.Array] = None,
                    effective_w=None, tables=None):
    """kind: full | local | chunked | bidir | cross.

    Returns (y, new_cache). cache = {"k","v"} of (B, S, Hkv, D); for
    mode="prefill" the produced K/V are returned as the new cache; for
    mode="decode" the token's K/V are written at `pos`.

    The cache write runs under the ``kv_pool`` named scope (op metadata
    only), so a profiler trace attributes it to KV pool handling.

    tables (decode + prefill): (B, P) int32 per-slot block tables of a
    :class:`~repro.serve.cache.PagedCache` -- cache["k"/"v"] are then
    page POOLS of shape (n, page_size, Hkv * D) (every layer's pages in
    one flat pool, `tables` already offset to this layer's) and attention
    runs directly on the pool (:func:`paged_decode_attention` /
    :func:`paged_prefill_attention`); for mode="prefill", `pos` carries
    the (B,) real prompt lengths.  The tables ride OUTSIDE the
    (donated) cache tree so the device copy survives across steps.
    """
    b, s, _ = x.shape
    h, hkv, hd = cfg.h_eff, cfg.hkv_eff, cfg.head_dim
    getw = effective_w or (lambda pp: pp["w"])
    kv_src = kv_input if kv_input is not None else x

    q = linear(x, getw(p["wq"]))
    kk = linear(kv_src, getw(p["wk"]))
    vv = linear(kv_src, getw(p["wv"]))
    q = sharding.constrain(q, "batch", None, "heads_flat")
    q = q.reshape(b, s, h, hd)
    kk = kk.reshape(b, kv_src.shape[1], hkv, hd)
    vv = vv.reshape(b, kv_src.shape[1], hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        kk = rmsnorm(kk, p["k_norm"], cfg.norm_eps)
    if cfg.attn_scale:
        # every attention path scales by 1/sqrt(hd); fold the rest into q
        q = q * jnp.asarray(cfg.attn_scale * math.sqrt(hd), q.dtype)

    def pe(t, pos):            # the position embedding (none under NoPE)
        return rope(t, pos, cfg.rope_theta) if cfg.use_rope else t

    causal = kind not in ("bidir", "cross")
    window = cfg.local_window if kind in ("local", "chunked") else 0
    chunked = kind == "chunked"

    if kind == "cross":
        if mode == "decode":
            k_all, v_all = cache["k"], cache["v"]   # precomputed encoder KV
            new_cache = cache
            out = decode_attention(q, k_all, v_all, jnp.asarray(
                k_all.shape[1] - 1), cap=cfg.attn_softcap)
        else:
            out = flash_attention(q, kk, vv, causal=False,
                                  cap=cfg.attn_softcap)
            new_cache = {"k": kk, "v": vv}
    elif mode == "decode":
        posn = jnp.asarray(pos)
        # () pos: one shared position; (B,) pos: per-slot positions
        # (continuous batching), rope/cache-write/mask all row-wise.
        pos_rope = posn[None] if posn.ndim == 0 else posn[:, None]
        q = pe(q, pos_rope)
        kk = pe(kk, pos_rope)
        if cache is not None and tables is not None:
            # paged KV (serve.cache.PagedCache): cache["k"/"v"] are page
            # pools (n, page_size, hkv * hd), `tables` the per-slot
            # block tables (B, P) of this layer's page ids.  The
            # step's only cache write is the token's (B,) K/V rows
            # scattered at (tables[b, pos//ps], pos%ps) -- with the tree
            # donated this is an in-place page write -- and attention
            # reads the pool in place (null / never-written pages are
            # skipped, stale page content only ever sits at masked
            # positions).
            page_size = cache["k"].shape[1]
            pos_b = jnp.broadcast_to(posn, (b,)) if posn.ndim == 0 \
                else posn                                # (B,)
            rows = jnp.arange(b)
            with jax.named_scope("kv_pool"):
                phys = tables[rows, pos_b // page_size]      # (B,)
                off = pos_b % page_size
                ck = cache["k"].at[phys, off].set(
                    kk[:, 0].reshape(b, hkv * hd).astype(cache["k"].dtype))
                cv = cache["v"].at[phys, off].set(
                    vv[:, 0].reshape(b, hkv * hd).astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            out = paged_decode_attention(q, ck, cv, tables, pos_b,
                                         window=window, chunked=chunked,
                                         cap=cfg.attn_softcap)
        else:
            if cache is not None:
                kk = kk.astype(cache["k"].dtype)
                vv = vv.astype(cache["v"].dtype)
                with jax.named_scope("kv_pool"):
                    if posn.ndim == 0:
                        ck = jax.lax.dynamic_update_slice_in_dim(
                            cache["k"], kk, posn, 1)
                        cv = jax.lax.dynamic_update_slice_in_dim(
                            cache["v"], vv, posn, 1)
                    else:
                        rows = jnp.arange(b)
                        ck = cache["k"].at[rows, posn].set(kk[:, 0])
                        cv = cache["v"].at[rows, posn].set(vv[:, 0])
            else:
                ck, cv = kk, vv
            new_cache = {"k": ck, "v": cv}
            out = decode_attention(q, ck, cv, posn, window=window,
                                   chunked=chunked, cap=cfg.attn_softcap)
    else:
        positions = jnp.arange(s)
        q = pe(q, positions)
        kk = pe(kk, positions)
        if mode == "prefill" and cache is not None and tables is not None:
            # paged prefill (serve.cache.PagedCache): cache["k"/"v"] are
            # page pools, `tables` the per-slot block tables (B, P), and
            # `pos` the (B,) REAL prompt lengths (rows at or beyond it
            # are padding).  Prompt K/V is scattered straight into the
            # slot's pages -- with the tree donated this writes the pool
            # in place -- and attention reads the pool directly.  Padded
            # rows are routed out of bounds and dropped so the pool (in
            # particular the shared null page) only ever holds real
            # tokens; garbage past a partial page's tail never exists.
            page_size = cache["k"].shape[1]
            lens_b = jnp.broadcast_to(jnp.asarray(pos), (b,))       # (B,)
            with jax.named_scope("kv_pool"):
                pg = jnp.minimum(positions // page_size,
                                 tables.shape[1] - 1)               # (S,)
                phys = tables[jnp.arange(b)[:, None], pg[None, :]]  # (B,S)
                phys = jnp.where(positions[None, :] < lens_b[:, None],
                                 phys, cache["k"].shape[0])         # OOB
                off = jnp.broadcast_to(positions[None, :] % page_size,
                                       (b, s))
                ck = cache["k"].at[phys, off].set(
                    kk.reshape(b, s, hkv * hd).astype(cache["k"].dtype),
                    mode="drop")
                cv = cache["v"].at[phys, off].set(
                    vv.reshape(b, s, hkv * hd).astype(cache["v"].dtype),
                    mode="drop")
            new_cache = {"k": ck, "v": cv}
            out = paged_prefill_attention(q, ck, cv, tables, lens_b,
                                          window=window, chunked=chunked,
                                          cap=cfg.attn_softcap)
        else:
            out = flash_attention(q, kk, vv, causal=causal, window=window,
                                  chunked=chunked, cap=cfg.attn_softcap)
            new_cache = {"k": kk, "v": vv} if mode == "prefill" else None

    out = out.reshape(b, s, h * hd)
    y = linear(out, getw(p["wo"]))
    return y, new_cache


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def ffn_swiglu(p: dict, x: jax.Array, effective_w=None) -> jax.Array:
    getw = effective_w or (lambda pp: pp["w"])
    g = linear(x, getw(p["w_gate"]))
    u = linear(x, getw(p["w_up"]))
    h = jax.nn.silu(g) * u
    h = sharding.constrain(h, "batch", None, "mlp")
    return linear(h, getw(p["w_down"]))


def _moe_local(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity: Optional[int], e_offset, renorm: bool = False):
    """Per-shard MoE: x (T, D) local tokens; w_* (E_loc, ...) the experts
    this shard holds, numbered from ``e_offset`` among the router's
    outputs.  Returns this shard's part of the result, (T, D).

    Routing: the top-k of the router's softmax, or with ``renorm`` a
    softmax over the top-k logits alone (taken in f32).

    ``capacity`` None is dropless: one masked dense pass over the held
    experts, each row weighted by its gate for that expert (0 where the
    expert was not chosen), so a row's result depends on no other row.
    Otherwise each expert processes its top-``capacity`` local tokens by
    gate weight; overflow tokens are dropped (contribute 0 for that
    expert), matching Switch-style routing.
    """
    t, dm = x.shape
    e_loc = w_gate.shape[0]
    with jax.named_scope("router"):
        if renorm:
            logits = jnp.dot(x, router_w,
                             preferred_element_type=jnp.float32)
            top, ids = jax.lax.top_k(logits, top_k)        # (T, k)
            gates = jax.nn.softmax(top, axis=-1)
        else:
            logits = x @ router_w                          # (T, E)
            probs = jax.nn.softmax(logits, axis=-1)
            gates, ids = jax.lax.top_k(probs, top_k)       # (T, k)
    if capacity is None:
        held = e_offset + jnp.arange(e_loc)
        g = jnp.sum(jnp.where(ids[:, :, None] == held, gates[:, :, None],
                              0.0), axis=1).astype(jnp.float32)  # (T, E_loc)
        hh = (jax.nn.silu(jnp.einsum("td,edf->etf", x, w_gate))
              * jnp.einsum("td,edf->etf", x, w_up))
        o = jnp.einsum("etf,efd->etd", hh, w_down).astype(jnp.float32)
        y = jnp.sum(o * g.T[:, :, None], axis=0)
        return y.astype(x.dtype)
    y = jnp.zeros((t, dm), jnp.float32)
    for el in range(e_loc):
        eg = e_offset + el
        match = (ids == eg)
        gate_e = jnp.sum(gates * match, axis=-1)         # (T,)
        top_g, top_i = jax.lax.top_k(gate_e, min(capacity, t))
        xe = x[top_i]                                    # (C, D)
        hh = jax.nn.silu(xe @ w_gate[el]) * (xe @ w_up[el])
        oe = (hh @ w_down[el]).astype(jnp.float32)
        y = y.at[top_i].add(oe * top_g[:, None])
    return y.astype(x.dtype)


def moe_layer(p: dict, x: jax.Array, cfg, effective_w=None) -> jax.Array:
    """Top-k MoE over cfg.n_experts, experts sharded on 'model'.

    The banks hold ``cfg.experts_held`` experts, the router's first ones
    (all of them unless the layer is one expert-parallel shard); a
    ``capacity_factor`` of 0 routes dropless.  The shared expert
    (``dense_residual``) is added once."""
    getw = effective_w or (lambda pp: pp["w"])
    b, s, dm = x.shape
    mesh = sharding.get_mesh()
    rules = sharding.get_rules() or {}
    e = cfg.n_experts
    k = cfg.experts_per_token
    dropless = cfg.capacity_factor <= 0
    router_w = p["router"]["w"]
    wg, wu, wd = (getw(p["w_gate"]), getw(p["w_up"]), getw(p["w_down"]))

    tp = 1
    if mesh is not None and rules.get("experts"):
        tp = mesh.shape[rules["experts"]]
    batch_axes = rules.get("batch")
    if mesh is None or tp == 1:
        xx = x.reshape(b * s, dm)
        cap = None if dropless else max(
            1, int(math.ceil(b * s * k * cfg.capacity_factor / e)))
        y = _moe_local(xx, router_w, wg, wu, wd, top_k=k, capacity=cap,
                       e_offset=0, renorm=cfg.moe_renorm)
        out = y.reshape(b, s, dm)
    else:
        dp = 1
        for ax in (batch_axes if isinstance(batch_axes, tuple)
                   else (batch_axes,) if batch_axes else ()):
            dp *= mesh.shape[ax]
        t_loc = max(b // dp, 1) * s
        cap = None if dropless else max(
            1, int(math.ceil(t_loc * k * cfg.capacity_factor / e)))
        e_loc = cfg.experts_held // tp
        model_ax = rules["experts"]

        def shard_fn(xs, rw, wg_, wu_, wd_):
            t_b, t_s, t_d = xs.shape
            xx = xs.reshape(t_b * t_s, t_d)
            e_off = jax.lax.axis_index(model_ax) * e_loc
            y = _moe_local(xx, rw, wg_, wu_, wd_, top_k=k, capacity=cap,
                           e_offset=e_off, renorm=cfg.moe_renorm)
            y = jax.lax.psum(y, model_ax)
            return y.reshape(t_b, t_s, t_d)

        out = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(None, None),
                      P(model_ax, None, None), P(model_ax, None, None),
                      P(model_ax, None, None)),
            out_specs=P(batch_axes, None, None),
        )(x, router_w, wg, wu, wd)

    if cfg.dense_residual:
        with jax.named_scope("shared_expert"):
            out = out + ffn_swiglu(p["shared"], x, effective_w)
    return out


# ---------------------------------------------------------------------------
# Mamba-2 SSD mixer
# ---------------------------------------------------------------------------


def _causal_conv1d(x: jax.Array, w: jax.Array, mode: str,
                   conv_state: Optional[jax.Array],
                   bias: Optional[jax.Array] = None):
    """Depthwise causal conv. x: (B, S, C); w: (K, C); bias: (C,) or
    None.  Returns (y, new_conv_state (B, K-1, C))."""
    kk = w.shape[0]
    w = w.astype(x.dtype)   # bf16 compute (conv weights are tiny)
    if mode == "decode":
        window = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
        y = jnp.einsum("bkc,kc->bc", window, w)[:, None, :]
        new_state = window[:, 1:, :]
    else:
        pad = jnp.zeros((x.shape[0], kk - 1, x.shape[2]), x.dtype)
        xp = jnp.concatenate([pad, x], axis=1)
        y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :]
                for i in range(kk))
        new_state = xp[:, xp.shape[1] - (kk - 1):, :]
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return y, new_state


def mamba2_layer(p: dict, x: jax.Array, cfg, *, mode: str = "train",
                 state=None, effective_w=None):
    """Mamba-2 (SSD) mixer. x: (B, S, D).

    Projections are kept separate (z / x / B / C / dt) so each output dim
    has a clean sharding: d_inner and heads shard on 'model' ('ssm_inner'),
    the small B/C/dt streams stay replicated.

    state (decode): {"ssm": (B, H, P, N), "conv": {"x","b","c"}}.
    Returns (y, new_state) -- None for mode="train", the final state for
    "prefill"/"decode".
    """
    getw = effective_w or (lambda pp: pp["w"])
    b, s, _ = x.shape
    di, n, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
    nh = cfg.ssm_heads

    z = linear(x, getw(p["in_z"]))                          # (B,S,di)
    xs_pre = linear(x, getw(p["in_x"]))                     # (B,S,di)
    bb_pre = linear(x, getw(p["in_b"]))                     # (B,S,N)
    cc_pre = linear(x, getw(p["in_c"]))                     # (B,S,N)
    dt = linear(x, getw(p["in_dt"]))                        # (B,S,H)
    z = sharding.constrain(z, "batch", None, "ssm_inner")
    xs_pre = sharding.constrain(xs_pre, "batch", None, "ssm_inner")

    cst = None if state is None else state["conv"]
    xs_pre, ncx = _causal_conv1d(xs_pre, p["conv_x"], mode,
                                 None if cst is None else cst["x"],
                                 p.get("conv_bias_x"))
    bb_pre, ncb = _causal_conv1d(bb_pre, p["conv_b"], mode,
                                 None if cst is None else cst["b"],
                                 p.get("conv_bias_b"))
    cc_pre, ncc = _causal_conv1d(cc_pre, p["conv_c"], mode,
                                 None if cst is None else cst["c"],
                                 p.get("conv_bias_c"))
    new_conv = {"x": ncx, "b": ncb, "c": ncc}
    xs = jax.nn.silu(xs_pre).reshape(b, s, nh, hd)          # (B,S,H,P)
    bb = jax.nn.silu(bb_pre)                                # (B,S,N)
    cc = jax.nn.silu(cc_pre)                                # (B,S,N)
    dt = jax.nn.softplus(dt + p["dt_bias"]).astype(jnp.float32)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))            # (H,)
    dta = dt * a                                            # (B,S,H) <= 0
    xs_f = xs.astype(jnp.float32)
    bb_f = bb.astype(jnp.float32)
    cc_f = cc.astype(jnp.float32)

    if mode == "decode":
        # (B,H,P,N) state updated in place on TPU (kernels/ssm_decode)
        y, s_new = ssm_ops.ssm_decode_step(
            state["ssm"], xs_f[:, 0], dt[:, 0], a, bb_f[:, 0], cc_f[:, 0],
            p["d_skip"].astype(jnp.float32))
        y = y.reshape(b, 1, di)
        new_state = {"ssm": s_new, "conv": new_conv}
    else:
        # chunked SSD dual form, lax.scan over chunks: one chunk's (Q, Q, H)
        # decay matrix live at a time (memory O(B*Q^2*H), not O(B*S*Q*H));
        # the carried running state is exactly the inter-chunk recurrence
        # that kernels/ssd_scan implements standalone for the TPU path.
        q = min(cfg.ssm_chunk, s)
        if mode == "train":
            # training shapes must tile exactly -- fail loudly, a silent
            # divisor fallback would quietly shrink the chunk
            assert s % q == 0, (s, q)
        else:
            # serving prefill accepts arbitrary prompt lengths: largest
            # divisor of s that fits the chunk budget (prime lengths
            # degrade toward q=1 -- correct but slow; exact-length
            # prefill keeps the SSM state unpolluted by padding)
            while s % q:
                q -= 1
        nc = s // q
        tri = jnp.tril(jnp.ones((q, q), bool))
        # (nc, B, Q, ...) chunk-major for the scan
        xs_c = jnp.moveaxis(xs_f.reshape(b, nc, q, nh, hd), 1, 0)
        bb_c = jnp.moveaxis(bb_f.reshape(b, nc, q, n), 1, 0)
        cc_c = jnp.moveaxis(cc_f.reshape(b, nc, q, n), 1, 0)
        dt_c = jnp.moveaxis(dt.reshape(b, nc, q, nh), 1, 0)
        dta_c = jnp.moveaxis(dta.reshape(b, nc, q, nh), 1, 0)

        def chunk_body(s_prev, inp):
            xc, bc, cci, dtc, dtac = inp                    # (B,Q,...)
            lcum = jnp.cumsum(dtac, axis=1)                 # (B,Q,H)
            li = lcum[:, :, None, :] - lcum[:, None, :, :]  # (B,Q,Q,H)
            decay_qq = jnp.where(tri[None, :, :, None], jnp.exp(li), 0.0)
            scores = jnp.einsum("bqn,btn->bqt", cci, bc)[..., None] \
                * decay_qq                                  # (B,Q,Q,H)
            y_intra = jnp.einsum("bqth,bth,bthp->bqhp", scores, dtc, xc)
            # inter-chunk term from the carried prefix state
            dec_from_start = jnp.exp(lcum)                  # (B,Q,H)
            y_inter = jnp.einsum("bqh,bhpn,bqn->bqhp",
                                 dec_from_start, s_prev, cci)
            # state update: S <- exp(l_end) S + sum_t e^{l_end-l_t} B (dt x)
            dec_to_end = jnp.exp(lcum[:, -1:, :] - lcum)    # (B,Q,H)
            s_in = jnp.einsum("bth,bth,bthp,btn->bhpn",
                              dec_to_end, dtc, xc, bc)
            s_new = jnp.exp(lcum[:, -1, :])[..., None, None] * s_prev + s_in
            return s_new, y_intra + y_inter

        s0 = state["ssm"].astype(jnp.float32) if state is not None else \
            jnp.zeros((b, nh, hd, n), jnp.float32)
        final, y_c = jax.lax.scan(chunk_body, s0,
                                  (xs_c, bb_c, cc_c, dt_c, dta_c))
        y = jnp.moveaxis(y_c, 0, 1).reshape(b, s, nh, hd)
        y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
            * xs_f.reshape(b, s, nh, hd)
        y = y.reshape(b, s, di)
        new_state = None if mode == "train" else \
            {"ssm": final, "conv": new_conv}

    y = y.astype(x.dtype) * jax.nn.silu(z)
    y = rmsnorm(y, p["ssm_norm"], cfg.norm_eps)
    y = sharding.constrain(y, "batch", None, "ssm_inner")
    out = linear(y, getw(p["out_proj"]))
    return out, new_state
