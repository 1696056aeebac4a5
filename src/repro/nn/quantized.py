"""Packed mixed-precision linear layers for quantized serving (Fig. 3).

This is the canonical home of the deployment-side packing math: after the
search assigns per-output-channel bit-widths, a layer's channels are
reordered into contiguous per-precision groups (paper Fig. 3), bit-packed,
and served through one ``quant_matmul`` per group.  Three consumers share
this module so a plan packs byte-identically everywhere:

  * ``serve.engine.export_mixed_precision_layer`` (per-layer export API),
  * :class:`PackedLinear` -- the pytree weight object that the LM forward
    serves through its ``getw`` weight provider (plan-driven decode),
  * the kernel-level ``quant_matmul.ops.quantized_linear_apply``.

Activation quantization here is **per row** (per token): each row of the
flattened ``(tokens, features)`` input gets its own int8 scale.  Besides
being more accurate than a per-tensor scale, this makes the quantized
matmul *batch-invariant* -- a request decodes to the same tokens whether it
shares a continuous-batching step with 7 neighbours or runs alone, which
the serving parity tests rely on.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import discretize, quantizers
from repro.kernels.quant_matmul import ops as qops


def quantize_activations_per_row(x: jax.Array):
    """Symmetric int8 activation quantization with one scale per row.

    x: (M, K) float. Returns (xq int8 (M, K), sx (M, 1) f32).
    """
    x = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    return xq, sx


def pack_channelwise(w: np.ndarray, channel_bits: np.ndarray,
                     perm: np.ndarray | None = None):
    """Reorder + bit-pack one layer (paper Fig. 3).

    w: (C_out, C_in) float weights; channel_bits: (C_out,) ints (0 = pruned).
    ``perm`` overrides the reorder permutation (e.g. the one stored in a
    :class:`~repro.api.plan.CompressionPlan`); by default it is recomputed
    from ``channel_bits``.

    Returns ``(packed, perm, kept)`` where ``packed`` is
    ``[(bits, wq_packed (Ni, ceil(C_in*bits/8)) int8, scales (Ni,) f32),
    ...]`` in ascending-bits order -- ``C_in`` is zero-padded to a whole
    number of bytes, then packed in ``quant_matmul``'s planar layout --
    and ``kept`` counts the non-pruned channels.
    A fully-pruned layer yields ``packed == []`` and ``kept == 0``.
    """
    if perm is None:
        perm = discretize.reorder_permutations(
            {"gamma": {"l": channel_bits}})["l"]
    w_sorted = np.asarray(w)[perm]
    bits_sorted = np.asarray(channel_bits)[perm]
    # the scale is per row, so integerizing every row at each width and
    # keeping that width's rows gives the same bytes as integerizing the
    # group alone -- and one array shape per layer shape, not one per
    # group size, for the eager ops to compile
    w_dev = jnp.asarray(w_sorted)
    packed = []
    for b in sorted(set(int(x) for x in bits_sorted if x > 0)):
        sel = bits_sorted == b
        qi, scale = quantizers.integerize_weights(w_dev, b, 0)
        k = w_sorted.shape[1]
        per = 8 // b
        pad = (-k) % per
        qi_np = np.asarray(qi)[sel]
        if pad:
            qi_np = np.pad(qi_np, ((0, 0), (0, pad)))
        packed.append((b, jnp.asarray(qops.pack_weights(qi_np, b)),
                       jnp.asarray(np.asarray(scale)[sel, 0])))
    kept = int(np.sum(bits_sorted > 0))
    return packed, perm, kept


def mixed_precision_matmul(x: jax.Array, packed_layers) -> jax.Array:
    """Serve ``y = x @ W^T`` for a reordered mixed-precision layer: one
    quant_matmul per precision group, outputs concatenated (Fig. 3).

    x: (M, K) float; returns (M, kept) f32 in permuted (ascending-bits)
    channel order.  An empty ``packed_layers`` (fully-pruned layer) returns
    a well-defined zero-width (M, 0) result.
    """
    if not packed_layers:
        return jnp.zeros(x.shape[:-1] + (0,), jnp.float32)
    xq, sx_row = quantize_activations_per_row(x)
    one = jnp.asarray(1.0, jnp.float32)
    outs = [qops.quant_matmul(xq, wq, sw, one, w_bits=bits)
            for bits, wq, sw in packed_layers]
    return jnp.concatenate(outs, axis=-1) * sx_row


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedLinear:
    """A bit-packed mixed-precision weight, servable inside a jitted LM
    forward.

    Stands in for a dense ``(n_in, n_out)`` projection matrix: the LM's
    weight provider returns it instead of an array and ``blocks.linear``
    dispatches to :meth:`__call__`, which runs one ``quant_matmul`` per
    precision group and scatters the concatenated group outputs back to
    the original channel order (pruned channels stay exactly zero, the
    same semantics as the search's 0-bit effective weight).

    Registered as a pytree so parameter trees containing it can cross
    ``jax.jit`` boundaries; the packed buffers and scales are leaves, the
    bit-widths and dimensions are static aux data.
    """

    groups: tuple        # ((bits, wq_packed, scales), ...) ascending bits
    out_index: jax.Array  # (kept,) int32: original positions of kept chans
    n_in: int
    n_out: int

    @classmethod
    def from_dense(cls, w_in_out: np.ndarray, channel_bits: np.ndarray,
                   perm: np.ndarray | None = None) -> "PackedLinear":
        """Pack a ``(n_in, n_out)`` projection (the LM's ``w`` layout)."""
        w = np.asarray(w_in_out, np.float32)
        packed, perm, kept = pack_channelwise(w.T, channel_bits, perm=perm)
        return cls(groups=tuple(packed),
                   out_index=jnp.asarray(np.asarray(perm)[:kept], jnp.int32),
                   n_in=int(w.shape[0]), n_out=int(w.shape[1]))

    @property
    def kept(self) -> int:
        return int(self.out_index.shape[0])

    def __call__(self, x: jax.Array) -> jax.Array:
        # one named scope (op metadata only) over the activation
        # quantization, the per-group kernels and the Fig. 3 scatter, so a
        # profiler trace attributes the whole wrapper to the layer
        with jax.named_scope("qlinear"):
            lead = x.shape[:-1]
            x2 = x.reshape((-1, self.n_in))
            full = jnp.zeros((x2.shape[0], self.n_out), jnp.float32)
            if self.groups:
                y = mixed_precision_matmul(x2, self.groups)
                full = full.at[:, self.out_index].set(y)
            return full.reshape(lead + (self.n_out,)).astype(x.dtype)

    # ------------------------------------------------------------- pytree
    def tree_flatten(self):
        leaves = []
        bits = []
        for b, wq, sw in self.groups:
            leaves.extend((wq, sw))
            bits.append(int(b))
        leaves.append(self.out_index)
        return leaves, (tuple(bits), self.n_in, self.n_out)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        bits, n_in, n_out = aux
        groups = tuple((b, leaves[2 * i], leaves[2 * i + 1])
                       for i, b in enumerate(bits))
        return cls(groups=groups, out_index=leaves[-1],
                   n_in=n_in, n_out=n_out)
