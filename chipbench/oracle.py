"""The comparison that decides ``correct``.

For each sampled request the plain reference runs once over its prompt
and served tokens (teacher-forced), and every served token is judged by
its gap: how far its reference logit lies below the reference's best
logit at that position.  Greedy serving in bf16 picks the best token or
one that ties it to within bf16 rounding, so a sound run's widest gap is
small; a wrong token reads the spread of the logits.

The control reads the same gap for the token that the reference computed
one precision lower (``hidden(..., lowp=True)``) puts first.

The reference is the configuration's module (``reference.py`` unless
the configuration names another; see its contract there), passed in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SEQ_BUCKET = 512        # reference sequences are padded to a multiple
ROW_CHUNK = 256         # logit rows computed at once


def sample(finished: list, k: int, seed: int) -> list:
    """The request with the most served tokens, and ``k - 1`` more drawn
    from the seed, of the requests the window finished."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.tokens),
                                            -len(r.prompt), r.uid))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


@functools.partial(jax.jit, static_argnames=("ref", "vocab", "with_control"))
def _gaps(params, h, hc, tok, *, ref, vocab, with_control):
    lg = ref.logits(params, h, vocab=vocab)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    if not with_control:
        return best - got, best - got
    lc = ref.logits(params, hc, vocab=vocab, lowp=True)
    first = jnp.argmax(lc, axis=-1)
    ctl = jnp.take_along_axis(lg, first[:, None], axis=-1)[:, 0]
    return best - got, best - ctl


def gaps(params, bits, reqs: list, cfg, ref, control: bool = False):
    """Widest gap of the served tokens of ``reqs`` under the reference
    module ``ref`` and, with ``control``, of the control's first choices
    at the same positions.  Returns ``(served_gap, control_gap or
    None)``."""
    sb = (None if bits is None
          else jax.tree.map(jnp.asarray, ref.stack_bits(bits, cfg)))
    dm = ref.dims(cfg)
    served, ctl = 0.0, None if not control else 0.0
    for r in reqs:
        toks = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), toks[:-1]])
        s = len(seq)
        pad = -(-s // SEQ_BUCKET) * SEQ_BUCKET
        seq = np.pad(seq, (0, pad - s))
        h = ref.hidden(params, sb, jnp.asarray(seq), dims=dm)
        hc = (ref.hidden(params, sb, jnp.asarray(seq), dims=dm, lowp=True)
              if control else h)
        pos = np.arange(len(r.prompt) - 1, s)          # one per served token
        for c in range(0, len(pos), ROW_CHUNK):
            p = pos[c:c + ROW_CHUNK]
            n = len(p)
            p = np.pad(p, (0, ROW_CHUNK - n))
            t = np.pad(toks[c:c + ROW_CHUNK], (0, ROW_CHUNK - n))
            g, gc = _gaps(params, h[p], hc[p], jnp.asarray(t), ref=ref,
                          vocab=cfg.vocab, with_control=control)
            served = max(served, float(np.max(np.asarray(g)[:n])))
            if control:
                ctl = max(ctl, float(np.max(np.asarray(gc)[:n])))
    return served, ctl
