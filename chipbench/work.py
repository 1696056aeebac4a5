"""Operations and bytes that the served computation needs, from shapes.

Counted from the model's sizes and the plan, never from a kernel's padded
blocks, so any implementation of a layer is charged the same work.  The
peaks come from ``peaks.json``, keyed by ``device_kind``.

This is the dense decoder's module of the work contract, which every
work module keeps (a configuration names its module by the key
``"work"``; this one when the key is absent): ``model_flops``,
``qlinear_roofline_s`` and ``paged_decode_bytes``, with this file's
signatures.  ``peaks`` belongs to the device and stays here.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

PROJ_SHAPES = {          # (input features, output features) by projection
    "mixer.wq": ("d", "q"), "mixer.wk": ("d", "kv"), "mixer.wv": ("d", "kv"),
    "mixer.wo": ("q", "d"), "ffn.w_gate": ("d", "ff"),
    "ffn.w_up": ("d", "ff"), "ffn.w_down": ("ff", "d")}


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def _widths(cfg) -> dict:
    return {"d": cfg.d_model, "q": cfg.n_heads * cfg.head_dim,
            "kv": cfg.n_kv_heads * cfg.head_dim, "ff": cfg.d_ff}


def qlinear_groups(cfg, bits: dict) -> list:
    """``[(K, {bits: channels}), ...]``: one entry per planned projection
    of every layer, from plan groups ``blocks.l0.<proj>.sb<layer>``."""
    w = _widths(cfg)
    out = []
    for g, b in sorted(bits.items()):
        proj = ".".join(g.split(".")[2:-1])
        k = w[PROJ_SHAPES[proj][0]]
        vals, counts = np.unique(np.asarray(b), return_counts=True)
        out.append((k, {int(v): int(c) for v, c in zip(vals, counts)
                        if v > 0}))
    return out


def qlinear_call(k: int, chans: dict, m: int) -> tuple:
    """``(ops, bytes)`` of one planned projection on ``m`` real rows:
    ``2 m K N`` over surviving channels; packed weights at their bits,
    one f32 scale per channel, int8 activations with one f32 scale per
    row read once, and the bf16 output written once."""
    n = sum(chans.values())
    ops = 2 * m * k * n
    weights = sum(-(-k * b // 8) * c for b, c in chans.items())
    byts = weights + 4 * n + m * (k + 4) + 2 * m * n
    return ops, byts


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
    """Bytes one decode step's attention must move over all layers: the
    live K and V of each row's attended context, its query and output."""
    hd = cfg.head_dim
    kv = sum(ctx) * 2 * cfg.n_kv_heads * hd * 2
    qo = len(ctx) * 2 * cfg.n_heads * hd * 2
    return (kv + qo) * cfg.n_layers


def linear_params(cfg, bits: dict | None) -> int:
    """Weights of the layer projections that serve a token (pruned
    channels excluded), over all layers."""
    w = _widths(cfg)
    if bits is None:
        return cfg.n_layers * sum(w[a] * w[b]
                                  for a, b in PROJ_SHAPES.values())
    return sum(k * sum(ch.values()) for k, ch in qlinear_groups(cfg, bits))


def model_flops(cfg, bits: dict | None, prefill: list, decode_ctx: list
                ) -> float:
    """Operations the served model needs: each prompt of length L (its
    tokens at positions 0..L-1, logits for the last one) and each decoded
    row attending over ``ctx`` positions (with its logits)."""
    lin = 2 * linear_params(cfg, bits)
    att = 4 * cfg.n_heads * cfg.head_dim * cfg.n_layers  # QK and PV
    head = 2 * cfg.d_model * cfg.vocab
    f = 0.0
    for n in prefill:
        f += n * lin + att * n * (n + 1) / 2 + head
    for c in decode_ctx:
        f += lin + att * c + head
    return f
