"""Plain reference of the served granite-4.0-h-small stage, written from
the layer equations.

float32 throughout, every matmul at ``Precision.HIGHEST``, no cache, no
kernels, no batching: one prompt-plus-served-tokens sequence at a time.
The Mamba-2 mixer is the literal recurrence, one position after another
(``lax.scan``), not the chunked dual form the program prefills with.
It imports nothing of the program; it reads the benchmark's own weights
(``model.make_params``) and, for a plan, the per-channel bits the
configuration draws (``model.plan_bits``), and quantizes the weights
itself (``reference.qlinear``).

Layer equations (see the configuration's ``assumed``), with
``rms(x, g) = x / sqrt(mean(x^2) + eps) * (1 + g)`` and ``r`` the
residual multiplier:

    x0      = E[t] * embedding_multiplier
    Mamba-2 layer, h = rms(x, g1):
      z, u, B, C, dt = h Wz, h Wx, h Wb, h Wc, h Wdt
      u, B, C = silu(conv(u) + bu), silu(conv(B) + bB), silu(conv(C) + bC)
                (causal depthwise convolutions of width 4)
      dt      = softplus(dt + dt_bias);   A = -exp(A_log)
      s_t     = exp(dt_t A) s_{t-1} + (dt_t u_t) B_t^T   (per head, s_0 = 0)
      y_t     = s_t C_t + D u_t
      x      += r * rms(y * silu(z), g_ssm) Wout
    attention layer (GQA, no position embedding):
      x      += r * softmax(q k^T * attention_multiplier + causal) v Wo
    MoE after every mixer, h2 = rms(x, g2):
      l       = h2 R (all 72 experts);  the top 10 of l, softmax over them
      x      += r * (sum over held experts e of gate_e *
                     (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
                     + (silu(h2 Sg) * (h2 Su)) Sd)
    logits  = (rms(x, gf) / logits_scaling) H
              (H = E^T where the head is tied, else the tree's lm_head)

The held experts are the banks' (experts 0..E_held-1 of the router's
order): the same share as the program.  Planned projections (the Mamba
and attention projections and the shared expert) go through
``reference.qlinear``; the rest stays float.

``lowp=True`` is the control: every matmul operand rounded to float8
(e4m3), one step below the bf16 the configuration serves in, and
planned projections' activations at int4 (``reference.qlinear``).

The module keeps ``reference.py``'s contract: ``dims``, ``stack_bits``,
``hidden``, ``logits``.  ``hidden`` returns the final normed hidden
states already divided by ``logits_scaling``, so that ``logits`` is the
head alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import HI, _low, _mm, attention, qlinear, rms


def dims(cfg) -> tuple:
    """(mixers of the pattern, n_heads, head_dim, eps, attention
    multiplier, embedding multiplier, residual multiplier, logits
    scaling, experts per token)."""
    mixers = tuple("attn" if i == cfg.attn_every // 2 else "mamba"
                   for i in range(cfg.attn_every))
    return (mixers, cfg.n_heads, cfg.head_dim, cfg.norm_eps,
            cfg.attn_scale, cfg.embed_scale or math.sqrt(cfg.d_model),
            cfg.residual_scale,
            cfg.logits_scaling, cfg.experts_per_token)


def stack_bits(bits: dict, cfg) -> dict:
    """``{"l<i>": {"<proj>": (n_superblocks, N) int32}}`` from plan
    groups named ``blocks.l<i>.<proj>.sb<j>``."""
    out = {}
    for g, b in bits.items():
        _, lname, *mid, sb = g.split(".")
        out.setdefault(lname, {}).setdefault(".".join(mid), {})[
            int(sb[2:])] = b
    return {ln: {proj: np.stack([v[j] for j in sorted(v)]).astype(np.int32)
                 for proj, v in projs.items()}
            for ln, projs in out.items()}


def _get(tree, dotted):
    for k in dotted.split("."):
        tree = tree[k]
    return tree


def conv(u, w, bias):
    """Causal depthwise convolution: u (S, C), w (K, C), bias (C,)."""
    k = w.shape[0]
    up = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(up[i:i + u.shape[0]] * w[i] for i in range(k)) + bias


def recurrence(u, dt, a, b, c, d):
    """The Mamba-2 state, one position at a time.  u: (S, H, P); dt:
    (S, H); a, d: (H,); b, c: (S, N).  Returns y (S, H, P)."""
    h, p = u.shape[1:]
    n = b.shape[1]

    def step(s, inp):
        ut, dtt, bt, ct = inp
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * ut)[:, :, None] * bt[None, None, :]
        y = jnp.einsum("hpn,n->hp", s, ct, precision=HI) + d[:, None] * ut
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (u, dt, b, c))
    return y


def mamba(w, lin, x, eps, lowp):
    f32 = lambda name: w[name].astype(jnp.float32)   # noqa: E731
    s = x.shape[0]
    nh = w["a_log"].shape[0]
    z = lin(x, "mixer.in_z")
    u = jax.nn.silu(conv(lin(x, "mixer.in_x"), f32("conv_x"),
                         f32("conv_bias_x")))
    b = jax.nn.silu(conv(lin(x, "mixer.in_b"), f32("conv_b"),
                         f32("conv_bias_b")))
    c = jax.nn.silu(conv(lin(x, "mixer.in_c"), f32("conv_c"),
                         f32("conv_bias_c")))
    dt = jax.nn.softplus(lin(x, "mixer.in_dt") + f32("dt_bias"))
    u, b, c = _low(u, lowp), _low(b, lowp), _low(c, lowp)
    y = recurrence(u.reshape(s, nh, -1), dt, -jnp.exp(f32("a_log")), b, c,
                   f32("d_skip")).reshape(s, -1)
    y = rms(y * jax.nn.silu(z), w["ssm_norm"], eps)
    return lin(y, "mixer.out_proj")


def attend(lin, x, n_heads, hd, scale, lowp):
    s = x.shape[0]
    # reference.attention scales by 1/sqrt(hd): the rest is folded in q
    q = lin(x, "mixer.wq").reshape(s, n_heads, hd) * (scale * math.sqrt(hd))
    k = lin(x, "mixer.wk").reshape(s, -1, hd)
    v = lin(x, "mixer.wv").reshape(s, -1, hd)
    rep = n_heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    return lin(attention(q, k, v, lowp).reshape(s, n_heads * hd),
               "mixer.wo")


def moe(w, lin, x, top_k, lowp):
    """The held experts' part of the routed result, plus the shared
    expert.  w: the layer's ``ffn`` tree."""
    f32 = lambda name: _low(w[name]["w"].astype(jnp.float32), lowp)  # noqa
    top, ids = jax.lax.top_k(_mm(x, w["router"]["w"].astype(jnp.float32),
                                 lowp), top_k)
    gates = jax.nn.softmax(top, axis=-1)
    held = jnp.arange(w["w_gate"]["w"].shape[0])
    g = jnp.sum(jnp.where(ids[:, :, None] == held, gates[:, :, None], 0.0),
                axis=1)                                  # (S, E_held)
    xl = _low(x, lowp)
    hg = jnp.einsum("sd,edf->esf", xl, f32("w_gate"), precision=HI)
    hu = jnp.einsum("sd,edf->esf", xl, f32("w_up"), precision=HI)
    o = jnp.einsum("esf,efd->esd", _low(jax.nn.silu(hg) * hu, lowp),
                   f32("w_down"), precision=HI)
    routed = jnp.sum(o * g.T[:, :, None], axis=0)
    f = jax.nn.silu(lin(x, "ffn.shared.w_gate")) * lin(x, "ffn.shared.w_up")
    return routed + lin(f, "ffn.shared.w_down")


@functools.partial(jax.jit, static_argnames=("dims", "lowp"))
def hidden(params, bits, tokens, *, dims, lowp=False):
    """Final normed hidden states (S, d) f32 for one token sequence,
    divided by the logits scaling.  ``bits`` is :func:`stack_bits`'s
    tree, or None for float weights."""
    mixers, n_heads, hd, eps, att_scale, emb_scale, res_scale, \
        logit_scale, top_k = dims
    x = params["embed"]["w"][tokens].astype(jnp.float32) * emb_scale
    x = _low(x, lowp)
    nsb = params["blocks"]["l0"]["norm1"].shape[0]
    for j in range(nsb):
        for i, mixer in enumerate(mixers):
            ln = f"l{i}"
            w = jax.tree.map(lambda a: a[j], params["blocks"][ln])
            b = None if bits is None else \
                {k: v[j] for k, v in bits[ln].items()}

            def lin(t, name, w=w, b=b):
                return qlinear(t, _get(w, name)["w"],
                               None if b is None else b[name], lowp)

            h = rms(x, w["norm1"], eps)
            if mixer == "mamba":
                y = mamba(w["mixer"], lin, h, eps, lowp)
            else:
                y = attend(lin, h, n_heads, hd, att_scale, lowp)
            x = x + res_scale * y
            h2 = rms(x, w["norm2"], eps)
            x = x + res_scale * moe(w["ffn"], lin, h2, top_k, lowp)
    return rms(x, params["final_norm"], eps) / logit_scale


@functools.partial(jax.jit, static_argnames=("vocab", "lowp"))
def logits(params, h, *, vocab, lowp=False):
    """(n, d) hidden -> (n, vocab) f32 logits over the real vocabulary:
    the output head, or the embedding's rows where the tree has none
    (tied)."""
    if "lm_head" in params:
        head = params["lm_head"]["w"][:, :vocab].astype(jnp.float32)
    else:
        head = params["embed"]["w"][:vocab].astype(jnp.float32).T
    return _mm(h, head, lowp)
