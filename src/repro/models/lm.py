"""Generic LM covering all 10 assigned architectures.

One decoder implementation parameterized by ArchConfig:
  * layer "super-block" patterns (dense, local/global, chunked+MoE,
    hybrid mamba:attn with MoE every moe_every-th layer (jamba 7:1,
    granite 9:1), pure SSM, enc-dec)
  * jax.lax.scan over super-blocks (HLO size independent of depth) with
    optional remat
  * the paper's channel-wise MPS + pruning as a first-class mode: every
    projection weight can carry per-output-channel bit-width selection
    parameters; mode="search" computes effective weights (Eq. 5) and the
    differentiable size cost

Entry points:
  init_params(cfg, key)          -> params pytree (use jax.eval_shape for
                                    the dry-run; real init for training)
  logical_axes(cfg)              -> same-structure pytree of logical axis
                                    tuples (resolved via sharding.spec)
  loss_fn / prefill / decode_step
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import mps, sampling
from repro.distributed import sharding
from repro.nn import blocks
from repro.nn import quantized as nnq


# ---------------------------------------------------------------------------
# layer patterns
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str           # attn | attn_local | attn_chunked | attn_bidir | mamba
    ffn: Optional[str]   # dense | moe | None
    cross: bool = False


def block_pattern(cfg: ArchConfig) -> tuple[LayerSpec, ...]:
    """Decoder super-block pattern; n_layers % len(pattern) == 0."""
    if cfg.is_hybrid:
        # one attention layer in attn_every, at attn_every // 2 (jamba 4
        # of 8, granite 5 of 10), MoE on every moe_every-th layer (jamba
        # 2, granite 1)
        k = cfg.moe_every if cfg.is_moe else 0
        return tuple(
            LayerSpec("attn" if i == cfg.attn_every // 2 else "mamba",
                      "moe" if k and i % k == k - 1 else "dense")
            for i in range(cfg.attn_every))
    if cfg.is_ssm:
        return (LayerSpec("mamba", None),)
    if cfg.attn_pattern == "local_global":
        return (LayerSpec("attn_local", "dense"), LayerSpec("attn", "dense"))
    if cfg.attn_pattern == "chunked":
        ffn = "moe" if cfg.is_moe else "dense"
        return (LayerSpec("attn_chunked", ffn),) * 3 + (LayerSpec("attn",
                                                                  ffn),)
    ffn = "moe" if cfg.is_moe else "dense"
    if cfg.is_moe and cfg.moe_every > 1:
        return tuple(LayerSpec("attn", "moe" if i % cfg.moe_every ==
                               cfg.moe_every - 1 else "dense")
                     for i in range(cfg.moe_every))
    return (LayerSpec("attn", ffn, cross=cfg.is_encdec),)


def enc_pattern(cfg: ArchConfig) -> tuple[LayerSpec, ...]:
    return (LayerSpec("attn_bidir", "dense"),)


def n_superblocks(cfg: ArchConfig) -> int:
    pat = block_pattern(cfg)
    assert cfg.n_layers % len(pat) == 0, (cfg.name, len(pat))
    return cfg.n_layers // len(pat)


def padded_vocab(cfg: ArchConfig) -> int:
    return -(-cfg.vocab // 256) * 256


# ---------------------------------------------------------------------------
# init (params + logical axes, same traversal)
# ---------------------------------------------------------------------------

class _Builder:
    def __init__(self, key, dtype, mps_on: bool, precisions):
        self.key = key
        self.dtype = dtype
        self.mps_on = mps_on
        self.precisions = precisions
        self.counter = 0

    def w(self, shape, logical, scale=None, mps_ok=True, stack=None):
        """A linear weight {'w': arr[, 'gamma': ...]} with logical axes."""
        self.counter += 1
        k = jax.random.fold_in(self.key, self.counter)
        fan_in = shape[0] if len(shape) == 2 else shape[-2]
        scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
        full = (stack,) + shape if stack else shape
        llog = (("layers",) + tuple(logical)) if stack else tuple(logical)
        arr = jax.random.normal(k, full, self.dtype) * scale
        out = {"w": arr}
        log = {"w": llog}
        if self.mps_on and mps_ok:
            c_out = shape[-1]
            g = sampling.init_selection_logits(self.precisions, (c_out,))
            if stack:
                g = jnp.broadcast_to(g, (stack,) + g.shape).copy()
            out["gamma"] = g.astype(jnp.float32)
            log["gamma"] = (("layers",) if stack else ()) + (None, None)
        return out, log

    def vec(self, shape, logical, init=0.0, stack=None):
        full = (stack,) + shape if stack else shape
        llog = (("layers",) + tuple(logical)) if stack else tuple(logical)
        return jnp.full(full, init, self.dtype), llog


def _attn_params(b: _Builder, cfg: ArchConfig, nsb: int):
    h, hkv, hd, d = cfg.h_eff, cfg.hkv_eff, cfg.head_dim, cfg.d_model
    p, l = {}, {}
    p["wq"], l["wq"] = b.w((d, h * hd), ("w_embed", "heads_flat"), stack=nsb)
    p["wk"], l["wk"] = b.w((d, hkv * hd), ("w_embed", "kv_flat"), stack=nsb)
    p["wv"], l["wv"] = b.w((d, hkv * hd), ("w_embed", "kv_flat"), stack=nsb)
    p["wo"], l["wo"] = b.w((h * hd, d), ("heads_flat", "w_embed"), stack=nsb)
    if cfg.qk_norm:
        p["q_norm"], l["q_norm"] = b.vec((hd,), (None,), 0.0, stack=nsb)
        p["k_norm"], l["k_norm"] = b.vec((hd,), (None,), 0.0, stack=nsb)
    return p, l


def _ffn_params(b: _Builder, cfg: ArchConfig, nsb: int, d_ff: int):
    d = cfg.d_model
    p, l = {}, {}
    p["w_gate"], l["w_gate"] = b.w((d, d_ff), ("w_embed", "mlp"), stack=nsb)
    p["w_up"], l["w_up"] = b.w((d, d_ff), ("w_embed", "mlp"), stack=nsb)
    p["w_down"], l["w_down"] = b.w((d_ff, d), ("mlp", "w_embed"), stack=nsb)
    return p, l


def _moe_params(b: _Builder, cfg: ArchConfig, nsb: int):
    """The router scores all ``n_experts``; the banks hold the layer's
    ``experts_held`` (one expert-parallel shard's share)."""
    d, e, f = cfg.d_model, cfg.experts_held, cfg.expert_d_ff
    p, l = {}, {}
    rp, rl = b.w((d, cfg.n_experts), (None, None), mps_ok=False, stack=nsb)
    p["router"], l["router"] = rp, rl
    p["w_gate"], l["w_gate"] = b.w((e, d, f),
                                   ("experts", "w_embed", None), stack=nsb)
    p["w_up"], l["w_up"] = b.w((e, d, f),
                               ("experts", "w_embed", None), stack=nsb)
    p["w_down"], l["w_down"] = b.w((e, f, d),
                                   ("experts", None, "w_embed"), stack=nsb)
    if cfg.dense_residual:
        sp, sl = _ffn_params(b, cfg, nsb, cfg.d_ff)
        p["shared"], l["shared"] = sp, sl
    return p, l


def _mamba_params(b: _Builder, cfg: ArchConfig, nsb: int):
    d, di, n, h, kk = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                       cfg.ssm_heads, cfg.ssm_conv)
    p, l = {}, {}
    p["in_z"], l["in_z"] = b.w((d, di), ("w_embed", "ssm_inner"), stack=nsb)
    p["in_x"], l["in_x"] = b.w((d, di), ("w_embed", "ssm_inner"), stack=nsb)
    p["in_b"], l["in_b"] = b.w((d, n), ("w_embed", None), stack=nsb)
    p["in_c"], l["in_c"] = b.w((d, n), ("w_embed", None), stack=nsb)
    p["in_dt"], l["in_dt"] = b.w((d, h), ("w_embed", None), stack=nsb)
    p["out_proj"], l["out_proj"] = b.w((di, d), ("ssm_inner", "w_embed"),
                                       stack=nsb)
    p["conv_x"], l["conv_x"] = b.vec((kk, di), (None, "ssm_inner"), 0.1,
                                     stack=nsb)
    p["conv_b"], l["conv_b"] = b.vec((kk, n), (None, None), 0.1, stack=nsb)
    p["conv_c"], l["conv_c"] = b.vec((kk, n), (None, None), 0.1, stack=nsb)
    if cfg.ssm_conv_bias:
        p["conv_bias_x"], l["conv_bias_x"] = b.vec((di,), ("ssm_inner",),
                                                   0.0, stack=nsb)
        p["conv_bias_b"], l["conv_bias_b"] = b.vec((n,), (None,), 0.0,
                                                   stack=nsb)
        p["conv_bias_c"], l["conv_bias_c"] = b.vec((n,), (None,), 0.0,
                                                   stack=nsb)
    p["dt_bias"], l["dt_bias"] = b.vec((h,), (None,), 0.0, stack=nsb)
    p["a_log"], l["a_log"] = b.vec((h,), (None,), 0.0, stack=nsb)
    p["d_skip"], l["d_skip"] = b.vec((h,), (None,), 1.0, stack=nsb)
    p["ssm_norm"], l["ssm_norm"] = b.vec((di,), ("ssm_inner",), 0.0,
                                         stack=nsb)
    return p, l


def _layer_params(b: _Builder, cfg: ArchConfig, spec: LayerSpec, nsb: int):
    d = cfg.d_model
    p, l = {}, {}
    p["norm1"], l["norm1"] = b.vec((d,), (None,), 0.0, stack=nsb)
    if spec.mixer == "mamba":
        p["mixer"], l["mixer"] = _mamba_params(b, cfg, nsb)
    else:
        p["mixer"], l["mixer"] = _attn_params(b, cfg, nsb)
    if spec.cross:
        p["norm_cross"], l["norm_cross"] = b.vec((d,), (None,), 0.0,
                                                 stack=nsb)
        p["cross"], l["cross"] = _attn_params(b, cfg, nsb)
    if spec.ffn is not None:
        p["norm2"], l["norm2"] = b.vec((d,), (None,), 0.0, stack=nsb)
        if spec.ffn == "moe":
            p["ffn"], l["ffn"] = _moe_params(b, cfg, nsb)
        else:
            p["ffn"], l["ffn"] = _ffn_params(b, cfg, nsb, cfg.d_ff)
    return p, l


def _build(cfg: ArchConfig, key, mps_on: bool):
    dtype = jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16
    b = _Builder(key, dtype, mps_on, cfg.mps_precisions)
    nsb = n_superblocks(cfg)
    v = padded_vocab(cfg)
    d = cfg.d_model
    params, logical = {}, {}
    params["embed"], logical["embed"] = b.w(
        (v, d), ("vocab", "w_embed"), scale=0.02, mps_ok=False)
    pat = block_pattern(cfg)
    bp, bl = {}, {}
    for i, spec in enumerate(pat):
        bp[f"l{i}"], bl[f"l{i}"] = _layer_params(b, cfg, spec, nsb)
    params["blocks"], logical["blocks"] = bp, bl
    params["final_norm"], logical["final_norm"] = b.vec((d,), (None,), 0.0)
    if not cfg.tie_embeddings:
        params["lm_head"], logical["lm_head"] = b.w(
            (d, v), ("w_embed", "vocab"), scale=0.02, mps_ok=False)
    if cfg.is_encdec:
        ep, el = {}, {}
        epat = enc_pattern(cfg)
        n_enc_sb = cfg.enc_layers // len(epat)
        for i, spec in enumerate(epat):
            ep[f"l{i}"], el[f"l{i}"] = _layer_params(b, cfg, spec, n_enc_sb)
        params["enc_blocks"], logical["enc_blocks"] = ep, el
        params["enc_norm"], logical["enc_norm"] = b.vec((d,), (None,), 0.0)
    return params, logical


def init_params(cfg: ArchConfig, key, mps_on: bool = False):
    return _build(cfg, key, mps_on)[0]


def logical_axes(cfg: ArchConfig, mps_on: bool = False):
    captured = {}

    def f(k):
        p, l = _build(cfg, k, mps_on)
        captured["l"] = l
        return p

    jax.eval_shape(f, jax.random.key(0))
    return captured["l"]


def abstract_params(cfg: ArchConfig, mps_on: bool = False):
    return jax.eval_shape(lambda k: _build(cfg, k, mps_on)[0],
                          jax.random.key(0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _make_effective_w(ctx: Optional[mps.SearchCtx], precisions):
    """Weight-fetch hook. Always casts to the bf16 compute dtype AT THE
    POINT OF USE: the cast output inherits the (FSDP-sharded) layout, so
    the per-layer all-gather moves bf16 instead of the f32 master -- this
    halves the dominant weight-gather collective bytes and the gathered-
    weight memory for f32-master architectures (Perf iteration 4).

    Plan-quantized serving rides the same hook: when the parameter tree
    was bound to a CompressionPlan (``serve.engine.apply_plan``), ``w`` is
    a :class:`~repro.nn.quantized.PackedLinear` and the provider hands it
    through untouched -- ``blocks.linear`` then serves the bit-packed
    per-precision groups through ``mixed_precision_matmul``."""
    if ctx is None:
        def getw(pp):
            w = pp["w"]
            if isinstance(w, nnq.PackedLinear):
                return w
            return w.astype(jnp.bfloat16)
        return getw

    def getw(pp):
        w = pp["w"]
        if "gamma" not in pp:
            return w.astype(jnp.bfloat16)
        return mps.effective_weight(
            w.astype(jnp.float32), pp["gamma"], precisions, ctx,
            channel_axis=w.ndim - 1).astype(jnp.bfloat16)
    return getw


def _layer_apply(cfg, spec: LayerSpec, p, x, *, mode, cache, pos,
                 enc_out, getw, tables=None):
    if getw is None:
        getw = _make_effective_w(None, cfg.mps_precisions)
    mixer_kind = {"attn": "full", "attn_local": "local",
                  "attn_chunked": "chunked", "attn_bidir": "bidir"}
    new_cache = {}
    h = blocks.rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == "mamba":
        with jax.named_scope("mamba"):
            y, st = blocks.mamba2_layer(
                p["mixer"], h, cfg, mode=mode,
                state=None if cache is None else cache.get("mamba"),
                effective_w=getw)
        if st is not None:
            new_cache["mamba"] = st
    else:
        with jax.named_scope("attn"):
            y, kv = blocks.attention_layer(
                p["mixer"], h, cfg, kind=mixer_kind[spec.mixer],
                mode=("train" if mode == "train" else mode),
                cache=None if cache is None else cache.get("kv"),
                pos=pos, effective_w=getw, tables=tables)
        if kv is not None:
            new_cache["kv"] = kv
    x = x + _residual(cfg, y)
    if spec.cross and enc_out is not None:
        hc = blocks.rmsnorm(x, p["norm_cross"], cfg.norm_eps)
        with jax.named_scope("attn"):
            yc, ckv = blocks.attention_layer(
                p["cross"], hc, cfg, kind="cross",
                mode=("train" if mode == "train" else mode),
                cache=None if cache is None else cache.get("cross_kv"),
                pos=pos, kv_input=enc_out)
        if ckv is not None:
            new_cache["cross_kv"] = ckv
        x = x + yc
    if spec.ffn is not None:
        h2 = blocks.rmsnorm(x, p["norm2"], cfg.norm_eps)
        if spec.ffn == "moe":
            with jax.named_scope("moe"):
                y2 = blocks.moe_layer(p["ffn"], h2, cfg, effective_w=getw)
        else:
            with jax.named_scope("ffn"):
                y2 = blocks.ffn_swiglu(p["ffn"], h2, effective_w=getw)
        x = x + _residual(cfg, y2)
    return x, (new_cache or None)


def _residual(cfg, y):
    """A residual branch as it is added (``residual_scale`` times)."""
    if cfg.residual_scale == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_scale, y.dtype)


def _split_pools(caches, tables):
    """Paged serving: take the KV pools out of a stacked cache tree.

    Returns ``(pools, rest, n1)``.  ``pools`` maps each attention slot of
    the pattern to its ``{"k", "v"}`` pools, each ``(nsb, n1, page_size,
    hkv * hd)`` viewed as one flat ``(nsb * n1, page_size, hkv * hd)``
    array -- the reshape merges leading axes only, so it is a bitcast --
    in which super-block ``j`` owns pages ``[j * n1, (j + 1) * n1)`` (see
    :func:`layer_tables`).  ``rest`` is the remainder of the tree (SSM
    state), still stacked ``(nsb, ...)``.  Dense caches (``tables`` None)
    have no pools: everything is ``rest``."""
    if caches is None or tables is None:
        return {}, caches, 0
    pools, rest, n1 = {}, {}, 0
    for name, c in caches.items():
        if "kv" in c:
            n1 = c["kv"]["k"].shape[1]
            pools[name] = jax.tree.map(
                lambda a: a.reshape(-1, *a.shape[2:]), c["kv"])
        other = {k: v for k, v in c.items() if k != "kv"}
        if other:
            rest[name] = other
    return pools, (rest or None), n1


def _join_pools(pools, rest, n1):
    """Inverse of :func:`_split_pools` on a runner's outputs: the flat
    pools reshaped back to ``(nsb, n1, ...)`` beside the stacked rest."""
    out = {} if rest is None else {n: dict(c) for n, c in rest.items()}
    for name, kv in pools.items():
        out.setdefault(name, {})["kv"] = jax.tree.map(
            lambda a: a.reshape(-1, n1, *a.shape[1:]), kv)
    return out or None


def layer_tables(tables, j, n1: int):
    """Block tables of super-block ``j`` in the flat pool of
    :func:`_split_pools`: each live page id offset by ``j * n1``.  Null
    entries stay 0, so the null page of every layer is layer 0's page 0
    and the kernels' ``phys != 0`` liveness test is unchanged."""
    return jnp.where(tables != 0, tables + j * n1, 0)


def _layer_cache(blk_rest, pools, name):
    """One pattern slot's cache: its slice of the rest, plus its flat KV
    pools."""
    c = dict((blk_rest or {}).get(name) or {})
    if name in pools:
        c["kv"] = pools[name]
    return c or None


def _take_pools(nc, pools, name):
    """Thread a layer's updated flat pools into ``pools``; return what
    is left of its new cache (SSM state, or a dense cache) or None."""
    if nc is None:
        return None
    nc = dict(nc)
    if name in pools:
        pools[name] = nc.pop("kv")
    return nc or None


def _run_stack(cfg, pattern, stack_params, x, *, mode, caches, pos,
               enc_out, getw, remat: bool, blk_logical=None, tables=None):
    """scan over super-blocks. caches: pytree stacked on axis 0 or None.

    tables: paged block tables (B, P) of page ids, shared by every layer
    (one page id backs a token position across ALL layers).  The KV pools
    are not scanned: they ride in the scan carry as flat arrays
    (:func:`_split_pools`), the layer index comes from an ``arange`` in
    ``xs``, and each super-block reads and writes its own pages in place
    through :func:`layer_tables`.  Only SSM state (small, per slot) is
    sliced and stacked by the scan.

    blk_logical: logical-axis tree matching one *sliced* block (leading
    'layers' axis stripped). Constraining the sliced weights inside the
    body keeps them FSDP-sharded after the scan's dynamic-slice, so the
    per-layer all-gather stays INSIDE the loop -- without this, GSPMD
    hoists the resharding of the whole stacked parameter out of the loop
    and materializes every layer's gathered weights at once (165 GiB/dev
    for jamba-398B; see EXPERIMENTS.md Sec-Perf iteration 0).
    """
    pools, rest, n1 = _split_pools(caches, tables)
    nsb = jax.tree.leaves(stack_params)[0].shape[0]

    def block_fn(carry, xs):
        xv, pools = carry
        pools = dict(pools)
        in_dtype = xv.dtype
        blk_params, blk_rest, j = xs
        if blk_logical is not None and sharding.get_mesh() is not None:
            blk_params = jax.tree.map(
                lambda p, l: sharding.constrain(p, *l),
                blk_params, blk_logical)
        xv = sharding.constrain(xv, "batch", "act_seq", "embed")
        with jax.named_scope("kv_pool"):
            tables_j = layer_tables(tables, j, n1) if pools else tables
        new_rest = {}
        for i, spec in enumerate(pattern):
            name = f"l{i}"
            xv, nc = _layer_apply(cfg, spec, blk_params[name], xv,
                                  mode=mode,
                                  cache=_layer_cache(blk_rest, pools, name),
                                  pos=pos, enc_out=enc_out, getw=getw,
                                  tables=tables_j)
            nc = _take_pools(nc, pools, name)
            if nc is not None:
                new_rest[name] = nc
        return (xv.astype(in_dtype), pools), (new_rest or None)

    fn = block_fn
    if remat:
        fn = jax.checkpoint(block_fn,
                            policy=jax.checkpoint_policies.nothing_saveable)
    (x, pools), new_rest = jax.lax.scan(
        fn, (x, pools), (stack_params, rest, jnp.arange(nsb)))
    return x, _join_pools(pools, new_rest, n1)


def _run_stack_unrolled(cfg, pattern, per_sb_params, x, *, mode, caches,
                        pos, enc_out, getw, tables=None):
    """Python-unrolled counterpart of :func:`_run_stack` for parameter
    trees whose super-blocks are a tuple of per-block trees instead of one
    stacked pytree.  Plan-quantized serving needs this: each block's
    :class:`~repro.nn.quantized.PackedLinear` buffers have layer-dependent
    shapes (different per-precision channel counts), so they cannot be
    stacked for a ``lax.scan``.  Caches keep the stacked ``(nsb, ...)``
    layout of :func:`init_caches` / :func:`init_paged_caches`.  KV pools
    are never sliced: one flat pool threads from layer to layer and
    super-block ``j`` addresses its pages through :func:`layer_tables`
    (under the ``kv_pool`` scope).  Dense caches and SSM state are sliced
    per block and stacked again after the last; each layer runs under
    ``layer{n}``."""
    pools, rest, n1 = _split_pools(caches, tables)
    per_sb_rest = []
    for j, blk_params in enumerate(per_sb_params):
        with jax.named_scope("kv_pool"):
            blk_rest = None if rest is None else \
                jax.tree.map(lambda a: a[j], rest)
            tables_j = layer_tables(tables, j, n1) if pools else tables
        new_rest = {}
        for i, spec in enumerate(pattern):
            name = f"l{i}"
            with jax.named_scope(f"layer{j * len(pattern) + i}"):
                x, nc = _layer_apply(cfg, spec, blk_params[name], x,
                                     mode=mode,
                                     cache=_layer_cache(blk_rest, pools,
                                                        name),
                                     pos=pos, enc_out=enc_out, getw=getw,
                                     tables=tables_j)
            nc = _take_pools(nc, pools, name)
            if nc is not None:
                new_rest[name] = nc
        per_sb_rest.append(new_rest or None)
    stacked = None
    if any(c is not None for c in per_sb_rest):
        with jax.named_scope("kv_pool"):
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                                   *per_sb_rest)
    return x, _join_pools(pools, stacked, n1)


def _has_gamma(tree) -> bool:
    if isinstance(tree, dict):
        return "gamma" in tree or any(_has_gamma(v) for v in tree.values())
    return False


def _sliced_block_logical(cfg, mps_on: bool, key: str = "blocks"):
    """Logical axes of one scan-sliced super-block (leading 'layers'
    stripped from every leaf)."""
    log = logical_axes(cfg, mps_on=mps_on)[key]
    return jax.tree.map(
        lambda l: tuple(l[1:]) if l and l[0] == "layers" else tuple(l),
        log, is_leaf=lambda v: isinstance(v, tuple))


def _embed_in(cfg, params, batch):
    if "embeddings" in batch:                  # vlm/audio frontend stub
        x = batch["embeddings"]
    else:
        table = params["embed"]["w"].astype(jnp.bfloat16)
        x = jnp.take(table, batch["tokens"], axis=0)
        x = x * jnp.asarray(cfg.embed_scale or math.sqrt(cfg.d_model),
                            jnp.bfloat16)
    return x.astype(jnp.bfloat16)


def _encode(cfg, params, batch, getw=None):
    if "enc_embeddings" in batch:
        xe = batch["enc_embeddings"].astype(jnp.bfloat16)
    else:
        xe = _embed_in(cfg, params, batch)
    xe, _ = _run_stack(cfg, enc_pattern(cfg), params["enc_blocks"], xe,
                       mode="train", caches=None, pos=None, enc_out=None,
                       getw=getw, remat=cfg.remat,
                       blk_logical=_sliced_block_logical(
                           cfg, _has_gamma(params["enc_blocks"]),
                           "enc_blocks"))
    return blocks.rmsnorm(xe, params["enc_norm"], cfg.norm_eps)


def forward(cfg: ArchConfig, params, batch, *, mode: str = "train",
            caches=None, pos=None, ctx: Optional[mps.SearchCtx] = None,
            logits_mode: str = "full", last_pos=None, tables=None):
    """Returns (logits | hidden, new_caches).

    batch keys: tokens (B, S) int32 | embeddings (B, S, D) for stub
    frontends; + enc_embeddings/enc_tokens for enc-dec.
    mode: train | prefill | decode.
    logits_mode: "full" | "last" (final position only -- serving prefill
    never materializes (B, S, V)) | "hidden" (return the final hidden
    states; the caller computes logits, e.g. the chunked loss below).
    last_pos: with logits_mode="last", an () int32 position to read
    instead of S-1 -- paged prefill pads the prompt to a q-chunk
    boundary and reads the logits of the last REAL token (causal
    attention makes every position <= last_pos independent of the
    padding).
    tables: paged serving -- (B, P) int32 block tables; `caches` KV
    leaves are then page pools (see ``init_paged_caches``) and the
    attention layers run the paged-attention kernels in place.  For
    mode="prefill" pass `pos` as the (B,) real prompt lengths; the
    prompt K/V is scattered straight into the slot's pages and
    attention reads the pool (no dense round-trip).
    """
    getw = _make_effective_w(ctx, cfg.mps_precisions)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(cfg, params, batch, getw)
    x = _embed_in(cfg, params, batch)
    remat = cfg.remat and mode == "train"
    if isinstance(params["blocks"], (list, tuple)):
        # plan-quantized serving tree (serve.engine.apply_plan): one tree
        # per super-block, PackedLinear weights, Python-unrolled
        x, new_caches = _run_stack_unrolled(
            cfg, block_pattern(cfg), params["blocks"], x, mode=mode,
            caches=caches, pos=pos, enc_out=enc_out, getw=getw,
            tables=tables)
    else:
        x, new_caches = _run_stack(
            cfg, block_pattern(cfg), params["blocks"], x, mode=mode,
            caches=caches, pos=pos, enc_out=enc_out, getw=getw,
            remat=remat,
            blk_logical=_sliced_block_logical(
                cfg, _has_gamma(params["blocks"])),
            tables=tables)
    x = blocks.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if logits_mode == "hidden":
        return x, new_caches
    if logits_mode == "last":
        if last_pos is None:
            x = x[:, -1:, :]
        else:
            x = jax.lax.dynamic_slice_in_dim(x, jnp.asarray(last_pos), 1,
                                             axis=1)
    with jax.named_scope("lm_head"):
        logits = _head(cfg, params, x)
    return logits, new_caches


def _head(cfg, params, x):
    """Output logits of hidden ``x`` (B, S, d): the head (the embedding
    transposed where they are tied), divided by ``logits_scaling``, then
    soft-capped."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"]["w"].astype(jnp.bfloat16))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["lm_head"]["w"].astype(jnp.bfloat16))
    logits = sharding.constrain(logits, "batch", None, "vocab")
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.final_softcap > 0:
        logits = blocks.softcap(logits, cfg.final_softcap)
    return logits


LOSS_SEQ_CHUNKS = 8


def loss_fn(cfg: ArchConfig, params, batch,
            ctx: Optional[mps.SearchCtx] = None,
            lam: float = 0.0):
    """Mean next-token cross-entropy (+ lambda * MPS size cost in search
    mode). Targets use the unpadded vocab range.

    The CE is computed over sequence chunks under jax.checkpoint so the
    f32 (B, S, V) logits are never materialized -- only (B, S/8, V/TP) is
    live at once, recomputed in the backward pass (Perf iteration 3:
    dropped peak temp memory ~40% on qwen3-32b train_4k).
    """
    hidden, _ = forward(cfg, params, batch, mode="train", ctx=ctx,
                        logits_mode="hidden")
    targets = batch["targets"]

    @jax.checkpoint
    def chunk_nll(x_c, tgt_c):
        logits = _head(cfg, params, x_c).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tgt_c[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - tgt)

    b, s, _ = hidden.shape
    nc = LOSS_SEQ_CHUNKS if s % LOSS_SEQ_CHUNKS == 0 else 1
    total = jnp.asarray(0.0, jnp.float32)
    for i in range(nc):
        sl = slice(i * (s // nc), (i + 1) * (s // nc))
        total = total + chunk_nll(hidden[:, sl], targets[:, sl])
    task = total / float(b * s)
    if ctx is not None and lam > 0.0:
        task = task + lam * mps_size_cost(cfg, params, ctx)
    return task


# ---------------------------------------------------------------------------
# the paper's cost model over the LM parameter tree
# ---------------------------------------------------------------------------


def mps_size_cost(cfg: ArchConfig, params, ctx: mps.SearchCtx) -> jax.Array:
    """Differentiable expected size (bytes) over all gamma-carrying weights
    (paper Eq. 9 with C_in fixed -- transformer residual streams keep
    d_model; pruning benefits show through the 0-bit channel count)."""
    precisions = cfg.mps_precisions
    total = jnp.asarray(0.0, jnp.float32)

    def visit(node):
        nonlocal total
        if isinstance(node, dict):
            if "w" in node and "gamma" in node:
                w, gm = node["w"], node["gamma"]
                cin = int(np.prod(w.shape[:-1]))
                if gm.ndim == 3:       # stacked over layers
                    cin = cin // gm.shape[0]
                    eb = jax.vmap(
                        lambda g: mps.expected_bits(g, precisions, ctx)
                    )(gm)
                else:
                    eb = mps.expected_bits(gm, precisions, ctx)
                total = total + jnp.sum(eb) * cin / 8.0
            else:
                for v in node.values():
                    visit(v)

    visit(params)
    return total


def mps_param_count(cfg: ArchConfig) -> int:
    """Number of gamma-carrying weight matrices (for reporting)."""
    tree = abstract_params(cfg, mps_on=True)
    n = 0

    def visit(node):
        nonlocal n
        if isinstance(node, dict):
            if "gamma" in node:
                n += 1
            for k, v in node.items():
                if isinstance(v, dict):
                    visit(v)

    visit(tree)
    return n


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, seq_len: int,
                enc_len: int = 0, abstract: bool = False):
    """KV / SSM caches stacked (n_superblocks, ...) per pattern slot."""
    nsb = n_superblocks(cfg)
    hkv, hd = cfg.hkv_eff, cfg.head_dim

    def mk(shape, dtype=jnp.bfloat16):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    caches = {}
    for i, spec in enumerate(block_pattern(cfg)):
        c = {}
        if spec.mixer == "mamba":
            c["mamba"] = {
                "ssm": mk((nsb, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32),
                "conv": {
                    "x": mk((nsb, batch, cfg.ssm_conv - 1, cfg.d_inner)),
                    "b": mk((nsb, batch, cfg.ssm_conv - 1, cfg.ssm_state)),
                    "c": mk((nsb, batch, cfg.ssm_conv - 1, cfg.ssm_state)),
                }}
        else:
            c["kv"] = {"k": mk((nsb, batch, seq_len, hkv, hd)),
                       "v": mk((nsb, batch, seq_len, hkv, hd))}
        if spec.cross:
            c["cross_kv"] = {"k": mk((nsb, batch, enc_len, hkv, hd)),
                             "v": mk((nsb, batch, enc_len, hkv, hd))}
        caches[f"l{i}"] = c
    return caches


def cache_logical_axes(cfg: ArchConfig):
    """Logical axes matching init_caches structure."""
    caches = {}
    for i, spec in enumerate(block_pattern(cfg)):
        c = {}
        if spec.mixer == "mamba":
            c["mamba"] = {
                "ssm": ("layers", "batch", "ssm_inner", None, None),
                "conv": {"x": ("layers", "batch", None, "ssm_inner"),
                         "b": ("layers", "batch", None, None),
                         "c": ("layers", "batch", None, None)}}
        else:
            c["kv"] = {"k": ("layers", "batch", "kv_seq", None, None),
                       "v": ("layers", "batch", "kv_seq", None, None)}
        if spec.cross:
            c["cross_kv"] = {
                "k": ("layers", "batch", None, None, None),
                "v": ("layers", "batch", None, None, None)}
        caches[f"l{i}"] = c
    return caches


def init_paged_caches(cfg: ArchConfig, batch: int, page_size: int,
                      n_pages: int, abstract: bool = False):
    """Paged counterpart of :func:`init_caches` (no cross-attention:
    serving is decoder-only).

    KV tensors become fixed page pools ``(nsb, n_pages + 1, page_size,
    hkv * hd)`` indexed by physical page id -- page 0 is the reserved null
    page that inactive block-table entries point at (written garbage is
    always masked).  A page is lane-dense: each token's KV heads lie side
    by side in one ``hkv * hd`` row, so a TPU stores the pool row-major
    with no padding, which is the layout the paged kernels read.  Inside
    a step the stack runners view each pool as one flat ``(nsb * (n_pages
    + 1), ...)`` array and address super-block ``j`` by page ids offset
    by ``j * (n_pages + 1)`` (:func:`layer_tables`), so no layer's pool is
    ever sliced out or stacked back.  SSM state is O(1) per request, so
    it keeps the dense per-slot layout ``(nsb, batch, ...)``.  The
    per-request block tables are NOT part of this tree; the cache backend
    composes them in at gather time (they are host-side bookkeeping that
    changes on admission / page allocation, not per decode step).
    """
    if cfg.is_encdec:
        raise NotImplementedError("paged caches are decoder-only")
    nsb = n_superblocks(cfg)
    hkv, hd = cfg.hkv_eff, cfg.head_dim

    def mk(shape, dtype=jnp.bfloat16):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    caches = {}
    for i, spec in enumerate(block_pattern(cfg)):
        c = {}
        if spec.mixer == "mamba":
            c["mamba"] = {
                "ssm": mk((nsb, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32),
                "conv": {
                    "x": mk((nsb, batch, cfg.ssm_conv - 1, cfg.d_inner)),
                    "b": mk((nsb, batch, cfg.ssm_conv - 1, cfg.ssm_state)),
                    "c": mk((nsb, batch, cfg.ssm_conv - 1, cfg.ssm_state)),
                }}
        else:
            c["kv"] = {"k": mk((nsb, n_pages + 1, page_size, hkv * hd)),
                       "v": mk((nsb, n_pages + 1, page_size, hkv * hd))}
        caches[f"l{i}"] = c
    return caches


def _tree_bytes(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(l.size * jnp.dtype(l.dtype).itemsize for l in leaves))


def kv_bytes_per_page(cfg: ArchConfig, page_size: int) -> int:
    """Bytes one page id pins across all attention layers' pools of
    :func:`init_paged_caches` (0 for pure-SSM architectures): the pools
    of a one-page tree (the null page alone)."""
    tree = init_paged_caches(cfg, 1, page_size, 0, abstract=True)
    return _tree_bytes({l: {"kv": c["kv"]} for l, c in tree.items()
                        if "kv" in c})


def ssm_bytes_per_slot(cfg: ArchConfig) -> int:
    """Bytes of recurrent (SSM + conv) state one decode slot pins (0 for
    attention-only architectures)."""
    tree = init_caches(cfg, 1, 1, abstract=True)
    return _tree_bytes({l: {"mamba": c["mamba"]} for l, c in tree.items()
                        if "mamba" in c})


def dense_cache_bytes(cfg: ArchConfig, batch: int, seq_len: int) -> int:
    """Total bytes :func:`init_caches` pins for a dense decode pool."""
    return _tree_bytes(init_caches(cfg, batch, seq_len, abstract=True))


def prefill(cfg: ArchConfig, params, batch):
    """Full-sequence forward producing logits + caches."""
    logits, caches = forward(cfg, params, batch, mode="prefill")
    return logits, caches


def decode_step(cfg: ArchConfig, params, token_batch, caches, pos,
                tables=None):
    """One-token decode. token_batch: {"tokens": (B, 1)} (or embeddings);
    pos: () int32 shared position, or (B,) int32 per-sequence positions
    (continuous batching: every slot decodes at its own offset).
    tables: (B, P) int32 block tables when `caches` holds page pools
    (paged serving); None for dense caches.
    Returns (logits (B, 1, V), caches)."""
    logits, new_caches = forward(cfg, params, token_batch, mode="decode",
                                 caches=caches, pos=pos, tables=tables)
    return logits, new_caches


# ---------------------------------------------------------------------------
# CompressionPlan group naming over the LM parameter tree
# ---------------------------------------------------------------------------
#
# Every 2-D projection that carries per-channel selection parameters in
# search mode is a plan group.  Weights are stacked (n_superblocks, K, N),
# so each (weight, super-block) pair gets its own group, named by the
# dotted parameter path plus the super-block index:
#
#     blocks.l0.mixer.wq.sb3, blocks.l1.ffn.w_down.sb0, ...
#
# MoE expert banks (4-D stacked) and the router stay float at serving
# time; embed / lm_head never carry gammas (mps_ok=False).


def _walk_plan_weights(cfg: ArchConfig, params):
    """Yield ``(dotted_path, template_node, param_node)`` for every
    plan-servable projection (gamma-carrying, 2-D per super-block)."""
    tmpl = abstract_params(cfg, mps_on=True)["blocks"]

    def visit(tnode, pnode, path):
        if not isinstance(tnode, dict):
            return
        if "w" in tnode and "gamma" in tnode and tnode["w"].ndim == 3:
            yield path, tnode, pnode
            return
        for k, tv in tnode.items():
            if isinstance(tv, dict):
                yield from visit(tv, pnode[k], f"{path}.{k}")

    for lname in tmpl:
        yield from visit(tmpl[lname], params["blocks"][lname],
                         f"blocks.{lname}")


def serve_weight_groups(cfg: ArchConfig, params) -> dict:
    """Plan-group name -> ``(C_out, C_in)`` float matrix for every
    quantizable LM projection -- the ``weights`` dict that
    ``serve.engine.export_plan_layers`` / ``CompressionPlan.bind`` take."""
    out = {}
    for path, _, pnode in _walk_plan_weights(cfg, params):
        w = np.asarray(pnode["w"], np.float32)        # (nsb, K, N)
        for j in range(w.shape[0]):
            out[f"{path}.sb{j}"] = w[j].T
    return out


def extract_plan(cfg: ArchConfig, params, px=(8,), meta=None):
    """Discretize an LM's per-channel selection logits into a
    :class:`~repro.api.plan.CompressionPlan` (paper Eq. 7/8 on the LM
    track).  ``params`` must carry gammas (``init_params(mps_on=True)``,
    e.g. after a ``make_train_step(search=True)`` run)."""
    from repro.api.plan import CompressionPlan

    pw = np.asarray(cfg.mps_precisions)
    gamma = {}
    for path, _, pnode in _walk_plan_weights(cfg, params):
        g = np.asarray(pnode["gamma"], np.float32)    # (nsb, C, |P|)
        bits = pw[np.argmax(g, axis=-1)]              # (nsb, C)
        for j in range(bits.shape[0]):
            gamma[f"{path}.sb{j}"] = bits[j]
    assignment = {"gamma": gamma, "delta": {}, "alpha": {}}
    base = {"track": "lm", "arch": cfg.name}
    return CompressionPlan.from_assignment(
        assignment, cfg.mps_precisions, px, meta={**base, **(meta or {})})
