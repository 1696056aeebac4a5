"""Deterministic synthetic datasets.

The container is offline, so the paper's datasets (CIFAR-10, GSC v2,
Tiny ImageNet) are replaced by synthetic sets with the *same tensor shapes
and class cardinalities* and enough structure to be learnable: each class
has a fixed smooth template; samples are template + noise + random shift.
Every batch is a pure function of (seed, step), which makes the input
pipeline stateless and trivially resumable after preemption (fault
tolerance) and identically shardable across hosts.
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ClassificationSpec:
    name: str
    shape: tuple[int, int, int]
    num_classes: int
    noise: float = 0.35


CIFAR10_LIKE = ClassificationSpec("cifar10-like", (32, 32, 3), 10)
GSC_LIKE = ClassificationSpec("gsc-like", (49, 10, 1), 12)
TINYIMAGENET_LIKE = ClassificationSpec("tinyimagenet-like", (64, 64, 3), 200)

DATASETS = {"cifar10": CIFAR10_LIKE, "gsc": GSC_LIKE,
            "tinyimagenet": TINYIMAGENET_LIKE}


def _templates(spec: ClassificationSpec) -> jax.Array:
    """Smooth per-class templates, fixed by the dataset name (through a
    stable checksum: ``hash()`` of a string changes with every process)."""
    key = jax.random.key(zlib.crc32(spec.name.encode()) % (2 ** 31))
    h, w, c = spec.shape
    # low-frequency template: upsampled coarse noise
    coarse = jax.random.normal(key, (spec.num_classes, max(h // 4, 1),
                                     max(w // 4, 1), c))
    t = jax.image.resize(coarse, (spec.num_classes, h, w, c), "linear")
    return t / jnp.maximum(jnp.std(t), 1e-6)


def class_batch(spec: ClassificationSpec, step: int, batch: int,
                seed: int = 0):
    """Pure function (spec, step, batch, seed) -> (x, y)."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed), step), 1)
    ky, kn, ks = jax.random.split(key, 3)
    y = jax.random.randint(ky, (batch,), 0, spec.num_classes)
    temps = _templates(spec)[y]
    noise = spec.noise * jax.random.normal(kn, (batch,) + spec.shape)
    shift = jax.random.randint(ks, (batch,), -2, 3)
    x = temps + noise
    x = jax.vmap(lambda img, s: jnp.roll(img, s, axis=1))(x, shift)
    return x, y


def eval_set(spec: ClassificationSpec, n_batches: int, batch: int,
             seed: int = 10_000):
    return [class_batch(spec, 10_000_000 + i, batch, seed)
            for i in range(n_batches)]


# ---------------------------------------------------------------------------
# LM token stream (for the 100M-scale end-to-end driver)
# ---------------------------------------------------------------------------

def lm_batch(vocab: int, seq_len: int, batch: int, step: int,
             seed: int = 0, structure: float = 0.9):
    """Deterministic learnable token stream.

    Tokens follow a noisy affine recurrence t[i+1] = (a*t[i] + b) % vocab
    with per-sequence (a, b) drawn from a tiny set, so a model can reduce
    loss well below uniform. Returns {"tokens", "targets"} of
    (batch, seq_len) int32.
    """
    key = jax.random.fold_in(jax.random.key(seed), step)
    k0, k1, k2, k3 = jax.random.split(key, 4)
    a = jnp.asarray([3, 5, 7, 11])[jax.random.randint(k0, (batch,), 0, 4)]
    b = jax.random.randint(k1, (batch,), 0, 13)
    t0 = jax.random.randint(k2, (batch,), 0, vocab)

    def step_fn(t, _):
        nxt = (a * t + b) % vocab
        return nxt, nxt

    _, toks = jax.lax.scan(step_fn, t0, None, length=seq_len)
    toks = jnp.swapaxes(toks, 0, 1)                      # (B, S)
    noise_mask = jax.random.bernoulli(k3, 1 - structure, toks.shape)
    noise = jax.random.randint(jax.random.fold_in(k3, 1), toks.shape, 0,
                               vocab)
    toks = jnp.where(noise_mask, noise, toks).astype(jnp.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
