"""Paged-attention decode kernel tests.

The contract under test (see src/repro/kernels/README.md):
  * kernel.py (interpret mode) agrees with ref.py's paged_attention_ref
    under jit to a few f32 ULP (KERNEL_REF_TOL) -- same per-page dots,
    same online-softmax update order;
  * ref.py's paged_attention_view (the off-TPU production path) is
    bitwise identical to blocks.decode_attention over the equivalent
    dense row (the PR 3 invariant);
  * null / never-written pages are skipped, not masked-after-read: a
    NaN-poisoned null page cannot reach the output;
  * the result depends only on the LOGICAL cache content -- physical
    page permutations, garbage in partial last pages, and freed
    mid-batch slots do not change live slots' outputs;
  * every layer's pages live in one flat pool, addressed by page ids
    offset by layer: attention and page writes through it equal those
    of separate per-layer pools, and a layer's writes never reach
    another layer's pages.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import kernel as pk
from repro.kernels.paged_attention import ops as pops
from repro.kernels.paged_attention import ref as pref
from repro.nn import blocks

import proptest as pt
from paged_pools import random_pools
from repro.configs import registry
from repro.models import lm

# kernel vs its ref: the same f32 online softmax, but whether each page's
# rescale-then-add (`l * corr + sum`, `acc * corr + pv`) is contracted
# into an FMA depends on how each graph is compiled, so they agree to a
# few ULP per page, not bitwise
KERNEL_REF_TOL = dict(rtol=1e-6, atol=1e-6)


def make_case(rng, lens, *, h=4, hkv=2, hd=16, ps=8, n_pb=4,
              n_pages=None, poison_null=False, poison_tail=None):
    """Build a pool + block tables for slots holding `lens` tokens each.

    Physical pages are drawn from a random permutation of the pool (so
    logical order != physical order); zero-length slots get an all-null
    table row (a freed / inactive slot).  ``poison_tail`` writes the
    given value into every allocated page position BEYOND the slot's
    live length (partial-last-page garbage)."""
    b = len(lens)
    if n_pages is None:
        n_pages = b * n_pb
    pool_k, pool_v = random_pools(rng, n_pages, ps, hkv, hd)
    if poison_null:
        pool_k[0] = np.nan
        pool_v[0] = np.nan
    tables = np.zeros((b, n_pb), np.int32)
    perm = rng.permutation(np.arange(1, n_pages + 1))
    idx = 0
    pos = np.zeros((b,), np.int32)
    for bi, n in enumerate(lens):
        npg = -(-n // ps)
        for p in range(npg):
            tables[bi, p] = perm[idx]
            idx += 1
        pos[bi] = max(n - 1, 0)
        if poison_tail is not None and npg:
            last = tables[bi, npg - 1]
            off = n - (npg - 1) * ps
            pool_k[last, off:] = poison_tail
            pool_v[last, off:] = poison_tail
    q = rng.normal(size=(b, h, hd)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables), jnp.asarray(pos))


def run(impl, case, **kw):
    fns = {"kernel": functools.partial(pk.paged_attention_fwd,
                                       interpret=True),
           "ref": pref.paged_attention_ref,
           "view": pref.paged_attention_view}
    return np.asarray(jax.jit(functools.partial(fns[impl], **kw))(*case))


class TestKernelVsRef:
    """kernel.py (interpret) must match the ref within KERNEL_REF_TOL."""

    @pytest.mark.parametrize("hkv", [1, 2, 4])
    def test_gqa_group_sizes(self, hkv):
        rng = np.random.default_rng(hkv)
        case = make_case(rng, (5, 17, 0), hkv=hkv, poison_null=True)
        # FMA contraction may differ per page: a few ULP
        np.testing.assert_allclose(run("kernel", case), run("ref", case),
                                   **KERNEL_REF_TOL)

    @pytest.mark.parametrize("window,chunked,cap", [
        (0, False, 0.0), (6, False, 0.0), (8, True, 0.0),
        (0, False, 30.0), (3, False, 50.0)])
    def test_mask_variants(self, window, chunked, cap):
        rng = np.random.default_rng(0)
        case = make_case(rng, (5, 17, 31), poison_null=True)
        kw = dict(window=window, chunked=chunked, cap=cap)
        # FMA contraction may differ per page: a few ULP
        np.testing.assert_allclose(run("kernel", case, **kw),
                                   run("ref", case, **kw), **KERNEL_REF_TOL)

    @pt.given(seed=pt.integers(0, 10**6))
    def test_property_random_layouts(self, seed):
        """Random slot counts, lengths, page sizes and physical page
        permutations: kernel == ref to a few ULP, both ~= the gathered
        view."""
        rng = np.random.default_rng(seed)
        ps = int(rng.choice([1, 2, 4, 8]))
        n_pb = int(rng.integers(1, 5))
        max_len = ps * n_pb
        b = int(rng.integers(1, 4))
        lens = tuple(int(rng.integers(0, max_len + 1)) for _ in range(b))
        hkv = int(rng.choice([1, 2]))
        q, pool_k, pool_v, tables, pos = make_case(
            rng, lens, hkv=hkv, ps=ps, n_pb=n_pb)
        poisoned = (q, pool_k.at[0].set(jnp.nan),
                    pool_v.at[0].set(jnp.nan), tables, pos)
        out_k = run("kernel", poisoned)
        out_r = run("ref", poisoned)
        # FMA contraction may differ per page: a few ULP
        np.testing.assert_allclose(out_k, out_r, **KERNEL_REF_TOL)
        assert np.isfinite(out_k).all()
        out_v = run("view", (q, pool_k, pool_v, tables, pos))
        for bi, n in enumerate(lens):
            if n > 0:           # view leaves inactive slots undefined
                np.testing.assert_allclose(out_k[bi], out_v[bi],
                                           rtol=2e-5, atol=2e-5)


class TestPoolSemantics:
    def test_view_bitwise_matches_dense_decode_attention(self):
        """Gathering the pages into logical order and running the dense
        decode-attention math must equal blocks.decode_attention on the
        equivalent dense cache row bit-for-bit (PR 3 invariant)."""
        rng = np.random.default_rng(1)
        lens = (5, 17, 26)
        ps, n_pb, hkv, hd = 8, 4, 2, 16
        q, pool_k, pool_v, tables, pos = make_case(
            rng, lens, hkv=hkv, hd=hd, ps=ps, n_pb=n_pb)
        # dense rows = the gathered view (stale content at masked
        # positions is irrelevant by construction of the mask)
        ck = np.asarray(pool_k)[np.asarray(tables)].reshape(
            len(lens), -1, hkv, hd)
        cv = np.asarray(pool_v)[np.asarray(tables)].reshape(
            len(lens), -1, hkv, hd)
        dense = jax.jit(blocks.decode_attention)(
            q[:, None], jnp.asarray(ck), jnp.asarray(cv), pos)
        view = jax.jit(pref.paged_attention_view)(
            q, pool_k, pool_v, tables, pos)
        np.testing.assert_array_equal(np.asarray(dense[:, 0]),
                                      np.asarray(view))

    def test_partial_last_page_garbage_is_ignored(self):
        lens = (5, 13)
        clean = make_case(np.random.default_rng(2), lens)
        dirty = make_case(np.random.default_rng(2), lens,
                          poison_tail=1e9)
        for impl in ("kernel", "ref", "view"):
            np.testing.assert_array_equal(run(impl, clean),
                                          run(impl, dirty))

    def test_null_page_is_skipped_not_masked(self):
        """NaN in the reserved null page must be unreachable: dead pages
        are skipped before any arithmetic (0 * NaN would still be NaN,
        so masking-after-read could not pass this)."""
        lens = (5, 17, 0)
        clean = make_case(np.random.default_rng(3), lens)
        poisoned = make_case(np.random.default_rng(3), lens,
                             poison_null=True)
        for impl in ("kernel", "ref"):
            out = run(impl, poisoned)
            assert np.isfinite(out).all()
            np.testing.assert_array_equal(out, run(impl, clean))

    def test_freed_slot_mid_batch(self):
        """Zeroing one slot's table row (free/preempt between steps)
        gives that slot a finite all-zero output and leaves the other
        slots bitwise untouched."""
        lens = (9, 20, 7)
        q, pk_, pv_, tables, pos = make_case(np.random.default_rng(4),
                                             lens, poison_null=True)
        freed_np = np.asarray(tables).copy()
        freed_np[1] = 0
        freed = jnp.asarray(freed_np)
        for impl in ("kernel", "ref"):
            before = run(impl, (q, pk_, pv_, tables, pos))
            after = run(impl, (q, pk_, pv_, freed, pos))
            np.testing.assert_array_equal(after[0], before[0])
            np.testing.assert_array_equal(after[2], before[2])
            np.testing.assert_array_equal(
                after[1], np.zeros_like(after[1]))

    def test_physical_permutation_invariance(self):
        """Two pools holding the same logical KV under different
        physical page layouts produce identical outputs."""
        rng = np.random.default_rng(5)
        lens = (9, 20)
        ps, n_pb, hkv, hd = 4, 8, 2, 16
        q, pk_a, pv_a, tables_a, pos = make_case(
            rng, lens, ps=ps, n_pb=n_pb, hkv=hkv, hd=hd)
        n_pages = pk_a.shape[0] - 1
        relayout = np.random.default_rng(6).permutation(
            np.arange(1, n_pages + 1))
        remap = np.zeros(n_pages + 1, np.int64)
        remap[1:] = relayout
        pk_b = np.zeros_like(np.asarray(pk_a))
        pv_b = np.zeros_like(np.asarray(pv_a))
        pk_b[remap[1:]] = np.asarray(pk_a)[1:]
        pv_b[remap[1:]] = np.asarray(pv_a)[1:]
        tables_b = remap[np.asarray(tables_a)].astype(np.int32)
        tables_b[np.asarray(tables_a) == 0] = 0
        case_b = (q, jnp.asarray(pk_b), jnp.asarray(pv_b),
                  jnp.asarray(tables_b), pos)
        for impl in ("kernel", "ref", "view"):
            np.testing.assert_array_equal(
                run(impl, (q, pk_a, pv_a, tables_a, pos)),
                run(impl, case_b))


class TestDispatch:
    def test_resolve_and_force(self):
        assert pops.resolve_impl("kernel") == "kernel"
        assert pops.resolve_impl() == ("kernel" if jax.default_backend()
                                       == "tpu" else "view")
        with pops.force_impl("ref"):
            assert pops.resolve_impl() == "ref"
        assert pops.resolve_impl() != "ref"
        with pytest.raises(ValueError, match="impl"):
            pops.resolve_impl("bogus")

    def test_ops_entry_point_all_impls_agree(self):
        case = make_case(np.random.default_rng(7), (6, 11))
        outs = {impl: np.asarray(jax.jit(functools.partial(
            pops.paged_attention, impl=impl))(*case))
            for impl in ("kernel", "ref", "view")}
        # FMA contraction may differ per page: a few ULP
        np.testing.assert_allclose(outs["kernel"], outs["ref"],
                                   **KERNEL_REF_TOL)
        np.testing.assert_allclose(outs["kernel"], outs["view"],
                                   rtol=2e-5, atol=2e-5)


class TestFlatLayerPool:
    """The stack runners hold every layer's pages in one flat pool and
    address layer ``j`` through ``tables + j * n1``: per layer, attention
    and its page writes equal those of a pool of its own."""

    LAYERS, N_PAGES, PS, N_PB = 3, 8, 4, 4

    @pytest.mark.parametrize("impl", ["kernel", "view"])
    @pytest.mark.parametrize("window", [0, 6])
    @pytest.mark.parametrize("mode", ["decode", "prefill"])
    def test_flat_pool_matches_per_layer_pools(self, mode, window, impl):
        cfg = dataclasses.replace(registry.get("llama3.2-1b-smoke"),
                                  local_window=window)
        hkv, hd, d = cfg.hkv_eff, cfg.head_dim, cfg.d_model
        rng = np.random.default_rng(11)
        n1 = self.N_PAGES + 1
        lens = np.asarray([5, 0, 11], np.int32)       # slot 1: null row
        b = len(lens)
        tables = np.zeros((b, self.N_PB), np.int32)
        perm = rng.permutation(np.arange(1, n1))
        idx = 0
        for bi, n in enumerate(lens):
            for pg in range(-(-(int(n) + 1) // self.PS) if n else 0):
                tables[bi, pg] = perm[idx]
                idx += 1
        pools = [random_pools(rng, self.N_PAGES, self.PS, hkv, hd)
                 for _ in range(self.LAYERS)]
        s = 1 if mode == "decode" else self.PS * self.N_PB
        xs = [jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
              for _ in range(self.LAYERS)]
        layers = [{w: {"w": jnp.asarray(rng.normal(size=shape) * 0.2,
                                        jnp.float32)}
                   for w, shape in (("wq", (d, cfg.h_eff * hd)),
                                    ("wk", (d, hkv * hd)),
                                    ("wv", (d, hkv * hd)),
                                    ("wo", (cfg.h_eff * hd, d)))}
                  for _ in range(self.LAYERS)]
        step = jax.jit(functools.partial(
            blocks.attention_layer, cfg=cfg, mode=mode,
            kind="local" if window else "full"))

        def layer(j, cache, tbl):
            return step(layers[j], xs[j], cache=cache, pos=lens,
                        tables=jnp.asarray(tbl))

        with pops.force_impl(impl):
            own = [layer(j, {"k": jnp.asarray(pk_), "v": jnp.asarray(pv_)},
                         tables)
                   for j, (pk_, pv_) in enumerate(pools)]
            flat = {"k": jnp.asarray(np.concatenate([p[0] for p in pools])),
                    "v": jnp.asarray(np.concatenate([p[1] for p in pools]))}
            for j in range(self.LAYERS):
                before = {k: np.asarray(v) for k, v in flat.items()}
                y, flat = layer(j, flat, lm.layer_tables(tables, j, n1))
                # real rows: a null row's and prefill padding's outputs
                # are discarded garbage read from the null page
                for bi, n in enumerate(lens):
                    rows = slice(0, 1 if mode == "decode" else n)
                    if n:
                        np.testing.assert_array_equal(
                            np.asarray(y)[bi, rows],
                            np.asarray(own[j][0])[bi, rows])
                for k in ("k", "v"):
                    after = np.asarray(flat[k])
                    # its own pages, null page aside, as in a pool of
                    # its own
                    np.testing.assert_array_equal(
                        after[j * n1 + 1:(j + 1) * n1],
                        np.asarray(own[j][1][k])[1:])
                    # every other layer's pages untouched
                    for o in range(self.LAYERS):
                        if o != j:
                            np.testing.assert_array_equal(
                                after[o * n1 + 1:(o + 1) * n1],
                                before[k][o * n1 + 1:(o + 1) * n1])
