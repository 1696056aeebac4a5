"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix fixes the loop (closed: each client sends its next request when
its last one finishes), the client count, the server's geometry and the
length distributions.  Lengths come in blocks of ``block`` requests that
hold exactly the mix's proportions: prompt lengths by their weights,
output lengths evenly spaced over ``[output_min, output_max]``.  The seed
pairs and orders them within each block and draws the prompt tokens, so
every seed serves the same sizes in another order.  ``pages`` sizes the
server's KV page pool.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
KEYS = ("loop", "clients", "max_batch", "max_len", "page_size", "pages",
        "prompt_lengths", "prompt_weights", "output_min", "output_max",
        "block", "greedy", "check_requests")


def load_mix(name: str) -> dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    check_mix(mix)
    return mix


def check_mix(mix: dict):
    missing = [k for k in KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    if mix["loop"] != "closed":
        raise ValueError(f"only closed loops are generated, got "
                         f"{mix['loop']!r}")
    if not mix["greedy"]:
        raise ValueError("the logit check needs greedy requests")
    w = np.asarray(mix["prompt_weights"], np.int64)
    if len(w) != len(mix["prompt_lengths"]) or mix["block"] % w.sum():
        raise ValueError("block must hold whole multiples of the prompt "
                         "weights")
    longest = max(mix["prompt_lengths"]) + mix["output_max"]
    if longest > mix["max_len"] or mix["max_len"] % mix["page_size"]:
        raise ValueError(f"max_len {mix['max_len']} must hold {longest} "
                         f"tokens and be a page multiple")


def decode_width(pos: int, mix: dict) -> int:
    """Pages of block table that a decode step whose highest position is
    ``pos`` attends over, by the server's rule (``InferenceServer.
    _live_width``): the live page count rounded up to an eighth of the
    table.  Each width is one compiled decode program."""
    tw = mix["max_len"] // mix["page_size"]
    step = max(1, tw // 8)
    need = pos // mix["page_size"] + 1
    return min(tw, -(-need // step) * step)


def warm_walks(mix: dict) -> list:
    """``[(prompt_length, n_steps), ...]``: requests whose decode steps,
    each the batch's highest position, reach every decode width a request
    of this mix can make the server pick, by the fewest steps.  A prompt
    of length L with ``n_steps + 1`` outputs decodes positions
    L .. L + n_steps - 1 (its first token comes from the prefill)."""
    best = {}                       # width -> (steps, prompt length)
    for n in sorted(mix["prompt_lengths"]):
        for pos in range(n, n + mix["output_max"] - 1):
            w = decode_width(pos, mix)
            if w not in best or pos - n + 1 < best[w][0]:
                best[w] = (pos - n + 1, n)
    walks = {n: 1 for n in mix["prompt_lengths"]}   # every prefill shape
    for steps, n in best.values():
        walks[n] = max(walks[n], steps)
    return sorted(walks.items())


class ClosedLoop:
    """Request shapes and prompt tokens, in order, for one seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        check_mix(mix)
        self.mix = mix
        self.vocab = int(vocab)
        self.rng = np.random.default_rng([int(seed), 7])
        w = np.asarray(mix["prompt_weights"], np.int64)
        reps = mix["block"] // int(w.sum())
        self._prompts = np.repeat(np.asarray(mix["prompt_lengths"]),
                                  w * reps)
        self._outputs = np.round(np.linspace(
            mix["output_min"], mix["output_max"], mix["block"])
        ).astype(np.int64)
        self._queue: list = []

    def next_shape(self) -> tuple:
        if not self._queue:
            p = self.rng.permutation(self._prompts)
            o = self.rng.permutation(self._outputs)
            self._queue = list(zip(p.tolist(), o.tolist()))[::-1]
        return self._queue.pop()

    def prompt(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def next_request(self) -> tuple:
        """``(prompt tokens, max_tokens)`` of the next request sent."""
        n, out = self.next_shape()
        return self.prompt(n), out
