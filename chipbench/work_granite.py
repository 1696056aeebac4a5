"""Operations and bytes that the served granite-4.0-h-small stage needs,
from shapes (the work contract of ``work.py``: ``model_flops``,
``qlinear_roofline_s`` and ``paged_decode_bytes``, with its signatures,
plus ``ssm_decode_bytes``).

Counted from the model's sizes and the plan, never from a kernel's
padded blocks.  The stage's super-block is ``attn_every`` layers, one of
them attention and the rest Mamba-2, each followed by the MoE.

Each token's MoE work is the shared expert (a planned projection,
counted with the others) plus the expected share of routed experts this
chip computes: ``experts_per_token * experts_held / n_experts`` experts
(10 * 9 / 72 = 1.25 for the cell), each ``3 d f`` multiply-adds, and the
router's ``d * n_experts``.  The masked dense pass the program runs
over every held expert for every row is not charged: the work is what
the routed rows need.
"""
from __future__ import annotations

from work import qlinear_call

# input features of each planned projection, by its path in the layer
PROJ_K = {"mixer.in_z": "d", "mixer.in_x": "d", "mixer.in_b": "d",
          "mixer.in_c": "d", "mixer.in_dt": "d", "mixer.out_proj": "di",
          "mixer.wq": "d", "mixer.wk": "d", "mixer.wv": "d",
          "mixer.wo": "q", "ffn.shared.w_gate": "d",
          "ffn.shared.w_up": "d", "ffn.shared.w_down": "ff"}


def _layers(cfg) -> tuple:
    """(attention layers, Mamba-2 layers) of the stage."""
    n_attn = cfg.n_layers // cfg.attn_every
    return n_attn, cfg.n_layers - n_attn


def _widths(cfg) -> dict:
    return {"d": cfg.d_model, "di": cfg.d_inner,
            "q": cfg.n_heads * cfg.head_dim, "ff": cfg.d_ff}


def qlinear_groups(cfg, bits: dict) -> list:
    """``[(K, {bits: channels}), ...]``: one entry per planned projection
    of every layer, from plan groups ``blocks.l<i>.<proj>.sb<j>``."""
    w = _widths(cfg)
    out = []
    for g, b in sorted(bits.items()):
        k = w[PROJ_K[".".join(g.split(".")[2:-1])]]
        chans = {}
        for v in b:
            if v > 0:
                chans[int(v)] = chans.get(int(v), 0) + 1
        out.append((k, chans))
    return out


def qlinear_roofline_s(cfg, bits: dict, rows: list, pk: dict) -> float:
    """Least time the chip could spend in the planned projections of
    calls on ``rows`` real rows each: per call, the larger of its
    operations over the int8 peak and its bytes over the memory peak."""
    groups = qlinear_groups(cfg, bits)
    total = 0.0
    for m in rows:
        for k, chans in groups:
            ops, byts = qlinear_call(k, chans, m)
            total += max(ops / pk["int8_ops"], byts / pk["hbm_bytes_s"])
    return total


def paged_decode_bytes(cfg, ctx: list) -> int:
    """Bytes one decode step's attention must move: the live K and V of
    each row's attended context, its query and output, in each attention
    layer."""
    hd = cfg.head_dim
    kv = sum(ctx) * 2 * cfg.n_kv_heads * hd * 2
    qo = len(ctx) * 2 * cfg.n_heads * hd * 2
    return (kv + qo) * _layers(cfg)[0]


def ssm_decode_bytes(cfg, rows: int) -> int:
    """Bytes one decode step's Mamba-2 state updates must move for
    ``rows`` decoded rows: each row's f32 state (heads x head_dim x
    d_state) read and written, its input x and output y (f32, heads x
    head_dim), B and C (f32, d_state each) and dt (f32, one a head), in
    each Mamba-2 layer."""
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    per_row = 4 * (2 * h * p * n + 2 * h * p + 2 * n + h)
    return rows * per_row * _layers(cfg)[1]


def linear_params(cfg, bits: dict | None) -> int:
    """Weights of the planned projections that serve a token (pruned
    channels excluded), over all layers."""
    if bits is not None:
        return sum(k * sum(ch.values()) for k, ch in qlinear_groups(cfg, bits))
    n_attn, n_mamba = _layers(cfg)
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    mamba = d * (2 * di + 2 * n + cfg.ssm_heads) + di * d
    attn = d * (q + 2 * kv) + q * d
    shared = 3 * d * cfg.d_ff
    return n_mamba * mamba + n_attn * attn + cfg.n_layers * shared


def model_flops(cfg, bits: dict | None, prefill: list, decode_ctx: list
                ) -> float:
    """Operations the served stage needs: each prompt of length L (its
    tokens at positions 0..L-1, logits for the last one) and each decoded
    row attending over ``ctx`` positions (with its logits).  Per token
    and layer besides the planned projections: the held share of routed
    experts and the router (every layer), the convolutions and the state
    update ``s' = exp(dt A) s + dt x B^T, y = s' C`` (5 operations a state
    value) of a Mamba-2 layer, and QK and PV of an attention layer."""
    n_attn, n_mamba = _layers(cfg)
    d = cfg.d_model
    routed = (cfg.experts_per_token * cfg.experts_held / cfg.n_experts
              * 3 * d * cfg.expert_d_ff)
    per_tok = (2 * linear_params(cfg, bits)
               + cfg.n_layers * 2 * (routed + d * cfg.n_experts)
               + n_mamba * (5 * cfg.ssm_heads * cfg.ssm_head_dim
                            * cfg.ssm_state
                            + 2 * cfg.ssm_conv * (cfg.d_inner
                                                  + 2 * cfg.ssm_state)))
    att = 4 * cfg.n_heads * cfg.head_dim * n_attn        # QK and PV
    head = 2 * d * cfg.vocab
    f = 0.0
    for n in prefill:
        f += n * per_tok + att * n * (n + 1) / 2 + head
    for c in decode_ctx:
        f += per_tok + att * c + head
    return f
