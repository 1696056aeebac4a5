"""Plain reference of the served model, written from the layer equations.

float32 throughout, every matmul at ``Precision.HIGHEST``, no cache, no
kernels, no batching: one prompt-plus-served-tokens sequence at a time,
full causal attention.  It imports nothing of the program; it reads the
benchmark's own weights (``model.make_params``) and, for a plan, the
per-channel bits the configuration draws (``model.plan_bits``) and
quantizes the weights itself.

Layer equations (the repository's dense decoder, see the configuration's
``assumed``):

    x0      = E[t] * sqrt(d)
    h       = rms(x) * (1 + g1);   q, k, v = h Wq, h Wk, h Wv
    q, k    = rope(q), rope(k)     (rotate-half, theta, per head of 64)
    x      += softmax(q k^T / sqrt(hd) + causal) v Wo
    h2      = rms(x) * (1 + g2)
    x      += (silu(h2 Wg) * (h2 Wu)) Wd
    logits  = (rms(x) * (1 + gf)) H

A planned projection computes, for output channel n at b > 0 bits,
``y[:, n] = (xq . q[:, n]) * s_n * sx`` with ``s_n = max|W[:, n]| /
(2^(b-1) - 1)``, ``q = clip(round(W / s_n))`` and per-row int8
activations ``sx = max|x_row| / 127``, ``xq = clip(round(x / sx))``;
0-bit channels give 0.

``lowp=True`` is the control: the same equations with every matmul
operand rounded to float8 (e4m3), one step below the bf16 the
configuration serves in, and planned projections' activations at int4.

This is the dense decoder's module of the reference contract, which
every reference module keeps (a configuration names its module by the
key ``"reference"``; this one when the key is absent):

    dims(cfg)                  static sizes ``hidden`` takes
    stack_bits(bits, cfg)      plan groups -> this module's layer stacking
    hidden(params, bits, tokens, *, dims, lowp=False)
    logits(params, h, *, vocab, lowp=False)

and may add ``plan_groups(cfg)`` where its plan covers weights that
``model.plan_groups`` does not walk.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PROJ = ("mixer.wq", "mixer.wk", "mixer.wv", "mixer.wo",
        "ffn.w_gate", "ffn.w_up", "ffn.w_down")


def dims(cfg) -> tuple:
    return (cfg.n_heads, cfg.head_dim, cfg.norm_eps, cfg.rope_theta)


def stack_bits(bits: dict, cfg) -> dict:
    """``{"mixer.wq": (n_layers, N) int32, ...}`` from plan groups named
    ``blocks.l0.<path>.sb<j>`` (one-layer super-blocks)."""
    out = {}
    for g, b in bits.items():
        _, lname, *mid, sb = g.split(".")
        if lname != "l0":
            raise ValueError(f"expected one layer per super-block: {g}")
        out.setdefault(".".join(mid), {})[int(sb[2:])] = b
    return {k: np.stack([v[j] for j in range(cfg.n_layers)]).astype(np.int32)
            for k, v in out.items()}


def _low(x, lowp):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if lowp else x


def _mm(x, w, lowp):
    return jnp.matmul(_low(x, lowp), _low(w, lowp), precision=HI)


def qlinear(x, w, bits, lowp=False):
    """x: (S, K) f32; w: (K, N); bits: (N,) int32 or None (float)."""
    w = w.astype(jnp.float32)
    if bits is None:
        return _mm(x, w, lowp)
    qmax_x = 7.0 if lowp else 127.0
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                     1e-8) / qmax_x
    xq = jnp.clip(jnp.round(x / sx), -qmax_x, qmax_x).astype(jnp.int8)
    live = bits > 0
    qmax = jnp.where(live, 2.0 ** (bits - 1) - 1, 1.0).astype(jnp.float32)
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / qmax
    q = jnp.clip(jnp.round(w / sw), -qmax, qmax)
    q = jnp.where(live, q, 0).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * jnp.where(live, sw, 0.0)


def rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def rope(x, theta):
    """x: (S, H, D)."""
    s, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, lowp, q_chunk=512):
    """Causal softmax attention, queries in chunks.  q, k, v: (S, H, D)."""
    s, h, d = q.shape
    qc = min(q_chunk, s)
    q, k, v = _low(q, lowp), _low(k, lowp), _low(v, lowp)

    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, 0)
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / math.sqrt(d)
        pos_q = i * qc + jnp.arange(qc)
        sc = jnp.where(jnp.arange(s)[None, None, :] <= pos_q[None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _low(p, lowp), v, precision=HI)

    return jax.lax.map(chunk, jnp.arange(s // qc)).reshape(s, h, d)


@functools.partial(jax.jit, static_argnames=("dims", "lowp"))
def hidden(params, bits, tokens, *, dims, lowp=False):
    """Final normed hidden states (S, d) f32 for one token sequence.
    ``dims`` = (n_heads, head_dim, eps, theta); ``bits`` maps each name
    of ``PROJ`` to (n_layers, N) int32, or is None for float weights."""
    n_heads, hd, eps, theta = dims
    d = params["embed"]["w"].shape[1]
    s = tokens.shape[0]
    x = params["embed"]["w"][tokens].astype(jnp.float32) * math.sqrt(d)
    x = _low(x, lowp)
    blk = params["blocks"]["l0"]
    layer_w = {name: blk[name.split(".")[0]][name.split(".")[1]]["w"]
               for name in PROJ}
    xs = ({n: layer_w[n] for n in PROJ}, blk["norm1"], blk["norm2"],
          None if bits is None else {n: bits[n] for n in PROJ})

    def layer(x, xs):
        w, g1, g2, b = xs
        lin = lambda t, n: qlinear(t, w[n], None if b is None else b[n],
                                   lowp)
        h = rms(x, g1, eps)
        q = rope(lin(h, "mixer.wq").reshape(s, n_heads, hd), theta)
        k = rope(lin(h, "mixer.wk").reshape(s, -1, hd), theta)
        v = lin(h, "mixer.wv").reshape(s, -1, hd)
        rep = n_heads // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        a = attention(q, k, v, lowp).reshape(s, n_heads * hd)
        x = x + lin(a, "mixer.wo")
        h2 = rms(x, g2, eps)
        f = jax.nn.silu(lin(h2, "ffn.w_gate")) * lin(h2, "ffn.w_up")
        return x + lin(f, "ffn.w_down"), None

    x, _ = jax.lax.scan(layer, x, xs)
    return rms(x, params["final_norm"], eps)


@functools.partial(jax.jit, static_argnames=("vocab", "lowp"))
def logits(params, h, *, vocab, lowp=False):
    """(n, d) hidden -> (n, vocab) f32 logits over the real vocabulary."""
    head = params["lm_head"]["w"][:, :vocab].astype(jnp.float32)
    return _mm(h, head, lowp)
