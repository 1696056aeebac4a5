"""Paged-attention prefill kernel tests (the decode matrix of
test_paged_attention.py, re-run for the q-chunked prefill kernel).

The contract under test (see src/repro/kernels/README.md):
  * prefill.py's kernel (interpret mode) agrees with paged_prefill_ref
    under jit to a few f32 ULP (KERNEL_REF_TOL) -- same per-(q-chunk,
    page) dots, same online-softmax update order -- and is independent
    of the q-chunk width to the same tolerance (each output row is an
    independent reduction);
  * paged_prefill_view (the off-TPU production path) is bitwise
    identical to blocks.flash_attention over the gathered dense rows
    whenever the gathered view is shape-matched to the dense input
    (q length == table_width * page_size) -- the prefill analogue of
    the decode PR 3 invariant;
  * null / never-written pages are skipped, not masked-after-read: a
    NaN-poisoned null page cannot reach any output row;
  * the result depends only on the LOGICAL pool content -- physical
    page permutations, garbage beyond a slot's live length, and freed
    mid-batch slots do not change live rows' outputs.  Rows at or
    beyond a slot's ``lens`` are discarded padding and carry no
    guarantees beyond finiteness.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops as pops
from repro.kernels.paged_attention import prefill as pf
from repro.nn import blocks

import proptest as pt
from paged_pools import random_pools

# kernel vs its ref: the same f32 online softmax, but whether each page's
# rescale-then-add (`l * corr + sum`, `acc * corr + pv`) is contracted
# into an FMA depends on how each graph is compiled, so they agree to a
# few ULP per page, not bitwise
KERNEL_REF_TOL = dict(rtol=1e-6, atol=1e-6)


def make_case(rng, lens, *, s=None, h=4, hkv=2, hd=16, ps=8, n_pb=4,
              n_pages=None, poison_null=False, poison_tail=None):
    """Pool + block tables for slots holding `lens` prompt tokens each,
    plus a (B, S) query batch (S covers the longest prompt, padded to a
    PREFILL_Q boundary unless given).  Physical pages are drawn from a
    random permutation (logical order != physical order); zero-length
    slots get an all-null table row.  ``poison_tail`` overwrites every
    allocated page position BEYOND the slot's live length."""
    b = len(lens)
    if s is None:
        s = -(-max(max(lens), 1) // pops.PREFILL_Q) * pops.PREFILL_Q
    if n_pages is None:
        n_pages = b * n_pb
    pool_k, pool_v = random_pools(rng, n_pages, ps, hkv, hd)
    if poison_null:
        pool_k[0] = np.nan
        pool_v[0] = np.nan
    tables = np.zeros((b, n_pb), np.int32)
    perm = rng.permutation(np.arange(1, n_pages + 1))
    idx = 0
    for bi, n in enumerate(lens):
        npg = -(-n // ps)
        for p in range(npg):
            tables[bi, p] = perm[idx]
            idx += 1
        if poison_tail is not None and npg:
            last = tables[bi, npg - 1]
            off = n - (npg - 1) * ps
            pool_k[last, off:] = poison_tail
            pool_v[last, off:] = poison_tail
    q = rng.normal(size=(b, s, h, hd)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables), jnp.asarray(lens, dtype=jnp.int32))


def run(impl, case, **kw):
    qc = pops.prefill_q_chunk(int(case[0].shape[1]))
    fns = {"kernel": functools.partial(pf.paged_prefill_fwd,
                                       interpret=True, q_chunk=qc),
           "ref": functools.partial(pf.paged_prefill_ref, q_chunk=qc),
           "view": pf.paged_prefill_view}
    return np.asarray(jax.jit(functools.partial(fns[impl], **kw))(*case))


def _real_rows(out_a, out_b, lens):
    for bi, n in enumerate(lens):
        yield out_a[bi, :n], out_b[bi, :n]


class TestKernelVsRef:
    """prefill.py (interpret) must match the ref within KERNEL_REF_TOL."""

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

    @pytest.mark.parametrize("q_chunk", [1, 2, 4, 8, 16])
    def test_q_chunk_width_invariance(self, q_chunk):
        """Every output row is an independent online-softmax reduction,
        so the tile width changes nothing beyond FMA rounding."""
        rng = np.random.default_rng(9)
        case = make_case(rng, (5, 17, 31), poison_null=True)
        # another tile shape is another compiled graph: a few ULP
        np.testing.assert_allclose(
            run("kernel", case, q_chunk=q_chunk),
            run("ref", case, q_chunk=16), **KERNEL_REF_TOL)

    @pt.given(seed=pt.integers(0, 10**6))
    def test_property_random_layouts(self, seed):
        """Random slot counts, prompt lengths, page sizes, GQA group
        sizes and physical page permutations: kernel == ref to a few ULP
        (NaN-poisoned null page), finite everywhere, both ~= the
        gathered view on real rows."""
        rng = np.random.default_rng(seed)
        ps = int(rng.choice([1, 2, 4, 8]))
        n_pb = int(rng.integers(1, 5))
        max_len = ps * n_pb
        b = int(rng.integers(1, 4))
        lens = tuple(int(rng.integers(0, max_len + 1)) for _ in range(b))
        hkv = int(rng.choice([1, 2]))
        q, pool_k, pool_v, tables, lens_a = make_case(
            rng, lens, s=max_len, hkv=hkv, ps=ps, n_pb=n_pb)
        poisoned = (q, pool_k.at[0].set(jnp.nan),
                    pool_v.at[0].set(jnp.nan), tables, lens_a)
        out_k = run("kernel", poisoned)
        out_r = run("ref", poisoned)
        # FMA contraction may differ per page: a few ULP
        np.testing.assert_allclose(out_k, out_r, **KERNEL_REF_TOL)
        assert np.isfinite(out_k).all()
        out_v = run("view", (q, pool_k, pool_v, tables, lens_a))
        for a, v in _real_rows(out_k, out_v, lens):
            np.testing.assert_allclose(a, v, rtol=2e-5, atol=2e-5)


class TestPoolSemantics:
    def test_view_bitwise_matches_dense_flash_attention(self):
        """Gathering the pages into logical order and running the dense
        flash-attention op sequence must equal blocks.flash_attention on
        the equivalent dense rows bit-for-bit when the gathered length
        matches the query length (the prefill PR 3 invariant; the
        serving parity matrix covers the padded general case at token
        granularity)."""
        rng = np.random.default_rng(1)
        for s in (16, 32, 48):
            ps, hkv, hd = 8, 2, 16
            n_pb = s // ps
            lens = (s, max(s - 7, 1), max(s - 19, 1))
            q, pool_k, pool_v, tables, lens_a = make_case(
                rng, lens, s=s, hkv=hkv, hd=hd, ps=ps, n_pb=n_pb,
                n_pages=3 * n_pb)
            ck = np.asarray(pool_k)[np.asarray(tables)].reshape(
                len(lens), -1, hkv, hd)
            cv = np.asarray(pool_v)[np.asarray(tables)].reshape(
                len(lens), -1, hkv, hd)
            dense = jax.jit(functools.partial(
                blocks.flash_attention, causal=True))(
                q, jnp.asarray(ck), jnp.asarray(cv))
            view = jax.jit(pf.paged_prefill_view)(
                q, pool_k, pool_v, tables, lens_a)
            np.testing.assert_array_equal(np.asarray(dense),
                                          np.asarray(view))

    def test_partial_last_page_garbage_is_ignored(self):
        """Real rows never see allocated-page positions at or beyond
        the slot's length (the causal mask excludes them), so garbage
        there cannot change them in ANY implementation."""
        lens = (5, 13)
        clean = make_case(np.random.default_rng(2), lens)
        dirty = make_case(np.random.default_rng(2), lens,
                          poison_tail=1e9)
        for impl in ("kernel", "ref", "view"):
            for a, b in _real_rows(run(impl, clean), run(impl, dirty),
                                   lens):
                np.testing.assert_array_equal(a, b)

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
        """Zeroing one slot's table row (free/preempt between requests)
        gives that slot finite all-zero rows and leaves the other
        slots bitwise untouched."""
        lens = (9, 20, 7)
        q, pk_, pv_, tables, lens_a = make_case(np.random.default_rng(4),
                                                lens, poison_null=True)
        freed_np = np.asarray(tables).copy()
        freed_np[1] = 0
        freed = jnp.asarray(freed_np)
        lens_freed = jnp.asarray([9, 0, 7], jnp.int32)
        for impl in ("kernel", "ref"):
            before = run(impl, (q, pk_, pv_, tables, lens_a))
            after = run(impl, (q, pk_, pv_, freed, lens_freed))
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
        q, pk_a, pv_a, tables_a, lens_a = make_case(
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
                  jnp.asarray(tables_b), lens_a)
        for impl in ("kernel", "ref", "view"):
            for a, b in _real_rows(
                    run(impl, (q, pk_a, pv_a, tables_a, lens_a)),
                    run(impl, case_b), lens):
                np.testing.assert_array_equal(a, b)


class TestDispatch:
    def test_prefill_q_chunk(self):
        assert pops.prefill_q_chunk(16) == 16
        assert pops.prefill_q_chunk(48) == 16
        assert pops.prefill_q_chunk(24) == 8
        assert pops.prefill_q_chunk(21) == 1

    def test_force_impl_pins_prefill_entry_point(self):
        case = make_case(np.random.default_rng(6), (6, 11))
        with pops.force_impl("ref"):
            pinned = np.asarray(jax.jit(pops.paged_prefill_attention)(
                *case))
        np.testing.assert_array_equal(pinned, run("ref", case))

    def test_ops_entry_point_all_impls_agree(self):
        lens = (6, 11)
        case = make_case(np.random.default_rng(7), lens)
        outs = {impl: np.asarray(jax.jit(functools.partial(
            pops.paged_prefill_attention, impl=impl))(*case))
            for impl in ("kernel", "ref", "view")}
        # FMA contraction may differ per page: a few ULP
        np.testing.assert_allclose(outs["kernel"], outs["ref"],
                                   **KERNEL_REF_TOL)
        for a, b in _real_rows(outs["kernel"], outs["view"], lens):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
