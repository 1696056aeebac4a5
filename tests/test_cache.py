"""CacheBackend tests: PagedCache page bookkeeping, the scheduler's
memory-aware admission contract (pool-exhaustion queuing, preemption
requeue ordering), page free-on-retire leak checks, paged-vs-dense
token-for-token parity across mixed prompt lengths (float + quantized,
greedy + seeded device sampling, streaming + preemption, gathered-view
AND Pallas-kernel attention impls), the paged-PREFILL conformance
matrix (solo/batched/streaming/preempted re-prefill under every prefill
impl, page-boundary prompt footprints, one bounded table upload per
admission), the device-resident block tables (no per-step host sync),
and the on-device sampling path vs. the host fallback."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import lm
from repro.serve import cache as cache_mod
from repro.serve import engine
from repro.serve.sampling import SamplingParams, make_rng, \
    sample_tokens_device
from repro.serve.scheduler import PendingEntry, Request, Scheduler, \
    SlotState


@pytest.fixture(scope="module")
def llama():
    cfg = registry.get("llama3.2-1b-smoke")
    return cfg, lm.init_params(cfg, jax.random.key(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
            for s in lens]


def _reqs(cfg, lens, sp, gap=0, seed=0):
    return [Request(uid=i, prompt=p, sampling=sp, arrival=gap * i)
            for i, p in enumerate(_prompts(cfg, lens, seed))]


# ---------------------------------------------------------------------------
# PagedCache bookkeeping (no model forward involved)
# ---------------------------------------------------------------------------

class TestPagedBookkeeping:
    def _backend(self, cfg, **kw):
        kw.setdefault("page_size", 8)
        kw.setdefault("n_pages", 6)
        kw.setdefault("reserve_pages", 1)
        return cache_mod.PagedCache(cfg, max_batch=2, max_len=32, **kw)

    def test_page_size_must_divide_max_len(self, llama):
        cfg, _ = llama
        with pytest.raises(ValueError, match="divide"):
            cache_mod.PagedCache(cfg, max_batch=2, max_len=32, page_size=5)
        with pytest.raises(ValueError, match="backend"):
            cache_mod.make_backend("ring", cfg, 2, 32)
        with pytest.raises(ValueError, match="no options"):
            cache_mod.make_backend("dense", cfg, 2, 32, page_size=8)

    def test_alloc_append_free_accounting(self, llama):
        cfg, _ = llama
        b = self._backend(cfg)
        base = b.memory_report()
        assert base["pages_in_use"] == 0
        # prompt of 7 + first decode write -> pages_for(8) = 1 page
        h = b.alloc(uid=0, slot=0, n_prompt=7)
        assert len(h.pages) == 1 and b.pages_in_use == 1
        b.append(h)             # next write pos 8 -> page boundary
        assert len(h.pages) == 2 and b.pages_in_use == 2
        for _ in range(7):
            b.append(h)         # pos 9..15: same page
        assert len(h.pages) == 2
        b.free(h)
        after = b.memory_report()
        assert after["pages_in_use"] == 0
        assert after["cache_bytes_in_use"] == 0
        assert after["peak_pages_in_use"] == 2
        assert after["peak_cache_bytes"] < after["dense_equivalent_bytes"]

    def test_admission_contract_and_exhaustion(self, llama):
        cfg, _ = llama
        b = self._backend(cfg)                     # 6 pages, reserve 1
        # 17-token prompt + first write -> 3 pages; +1 reserve -> needs 4
        assert b.can_admit(17)
        h0 = b.alloc(0, 0, 17)
        assert b.pages_in_use == 3
        assert not b.can_admit(17)                 # 3 free < 3 + reserve
        assert b.can_admit(7)                      # 1 + 1 reserve <= 3
        h1 = b.alloc(1, 1, 15)                     # 2 pages
        assert b.pages_in_use == 5
        # drive h0 to a boundary crossing with one free page: ok
        for _ in range(7):
            b.append(h0)                           # pos 18..24 (cross at 24)
        assert b.pages_in_use == 6
        # next crossing for h1 must raise
        with pytest.raises(cache_mod.PoolExhausted):
            for _ in range(16):
                b.append(h1)
        b.free(h0)
        b.free(h1)
        assert b.memory_report()["pages_in_use"] == 0

    def test_check_feasible(self, llama):
        cfg, _ = llama
        b = self._backend(cfg, n_pages=3)
        with pytest.raises(ValueError, match="never"):
            # 25 + 7 = 32 tokens -> 4 pages + 1 reserve > 3-page pool
            b.check_feasible(n_prompt=25, max_tokens=7)
        b.check_feasible(n_prompt=9, max_tokens=6)    # 2 pages + 1 fits

    def test_ssm_arch_needs_no_pages(self):
        cfg = registry.get("mamba2-780m-smoke")
        b = cache_mod.PagedCache(cfg, max_batch=2, max_len=32, page_size=8,
                                 n_pages=1)
        assert b.pages_for(100) == 0
        assert b.can_admit(31)
        assert b.memory_report()["bytes_per_page"] == 0
        assert b.memory_report()["ssm_slot_bytes"] > 0


# ---------------------------------------------------------------------------
# scheduler: memory-aware admission + preemption bookkeeping
# ---------------------------------------------------------------------------

class TestMemoryAwareScheduler:
    def _req(self, uid, s0=4, arrival=0, max_tokens=4):
        return Request(uid=uid, prompt=np.arange(s0, dtype=np.int32),
                       sampling=SamplingParams(max_tokens=max_tokens),
                       arrival=arrival)

    def _state(self, entry, slot):
        req = entry.request
        return SlotState(request=req, slot=slot,
                         pos=entry.tokens().size,
                         remaining=req.sampling.max_tokens,
                         last_token=0, out=list(entry.tokens()[
                             req.prompt.size:]),
                         rng=make_rng(req.sampling, req.uid))

    def test_memory_blocked_head_queues_fcfs(self):
        sched = Scheduler(max_batch=4, max_len=32)
        sched.submit(self._req(0, s0=20))     # big head
        sched.submit(self._req(1, s0=2))      # small behind it
        # gate rejects the big head -> nothing admits (no skip-ahead)
        assert sched.pop_admissible(
            0, can_admit=lambda e: e.tokens().size < 10) is None
        # gate opens -> FIFO resumes with the head
        entry, slot = sched.pop_admissible(0, can_admit=lambda e: True)
        assert entry.request.uid == 0

    def test_preempt_requeues_front_with_stream(self):
        sched = Scheduler(max_batch=2, max_len=32)
        for uid in range(2):
            sched.submit(self._req(uid))
        sched.submit(self._req(7, arrival=0))     # waits behind
        e0, s0 = sched.pop_admissible(0)
        st0 = self._state(e0, s0)
        st0.order = 1
        sched.activate(s0, st0)
        e1, s1 = sched.pop_admissible(0)
        st1 = self._state(e1, s1)
        st1.order = 2
        sched.activate(s1, st1)
        st1.out.extend([5, 6])                    # generated so far
        sched.preempt(s1)
        assert sched.preemptions == 1
        # the preempted request is FIRST in line (ahead of uid 7) and its
        # resume tokens carry prompt + generated stream
        entry, _ = sched.pop_admissible(0)
        assert entry.request.uid == 1 and entry.resume is st1
        np.testing.assert_array_equal(
            entry.tokens(),
            np.concatenate([entry.request.prompt, [5, 6]]).astype(np.int32))

    def test_successive_preemptions_keep_fcfs(self):
        sched = Scheduler(max_batch=2, max_len=32)
        for uid in range(2):
            sched.submit(self._req(uid))
        e0, s0 = sched.pop_admissible(0)
        st0 = self._state(e0, s0); st0.order = 1
        sched.activate(s0, st0)
        e1, s1 = sched.pop_admissible(0)
        st1 = self._state(e1, s1); st1.order = 2
        sched.activate(s1, st1)
        sched.preempt(s1)                  # youngest first
        sched.preempt(s0)                  # then the older one
        uids = [e.request.uid for e in sched.pending]
        assert uids == [0, 1]              # older resumes first

    def test_preempted_uid_still_counts_as_duplicate(self):
        sched = Scheduler(max_batch=1, max_len=32)
        sched.submit(self._req(3))
        e, s = sched.pop_admissible(0)
        sched.activate(s, self._state(e, s))
        sched.preempt(s)
        with pytest.raises(ValueError, match="duplicate"):
            sched.submit(self._req(3))


# ---------------------------------------------------------------------------
# end-to-end: paged == dense, token for token
# ---------------------------------------------------------------------------

class TestPagedDenseParity:
    def test_mixed_prompt_lengths_greedy_and_sampled(self, llama):
        cfg, params = llama
        dense = engine.InferenceServer(cfg, params, max_len=48, max_batch=2)
        paged = engine.InferenceServer(cfg, params, max_len=48, max_batch=2,
                                       cache="paged", page_size=8,
                                       pages=10)
        for sp, gap, seed in [
                (SamplingParams(max_tokens=6), 0, 0),
                (SamplingParams(temperature=0.8, top_k=12, max_tokens=5,
                                seed=11), 3, 1)]:
            lens = (4, 13, 7, 9)
            ref = dense.serve(_reqs(cfg, lens, sp, seed=seed))
            out = paged.serve(_reqs(cfg, lens, sp, gap=gap, seed=seed))
            for i in range(len(lens)):
                np.testing.assert_array_equal(ref[i], out[i])
        mem = paged.stats["memory"]
        assert mem["peak_cache_bytes"] < mem["dense_equivalent_bytes"]
        assert mem["pages_in_use"] == 0          # free-on-retire: no leak

    def test_quantized_plan_paged_parity_and_memory(self, llama):
        cfg, params = llama
        plan = engine.synthetic_plan(cfg, params, bits=None, seed=0)
        dense = engine.InferenceServer(cfg, params, plan=plan, max_len=48,
                                       max_batch=2)
        paged = engine.InferenceServer(cfg, params, plan=plan, max_len=48,
                                       max_batch=2, cache="paged",
                                       page_size=8, pages=9)
        sp = SamplingParams(max_tokens=6)
        lens = (5, 11, 8)
        ref = dense.serve(_reqs(cfg, lens, sp, seed=2))
        out = paged.serve(_reqs(cfg, lens, sp, gap=2, seed=2))
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref[i], out[i])
        mem = paged.stats["memory"]
        assert mem["pages_in_use"] == 0
        assert 0 < mem["peak_cache_bytes"] < mem["dense_equivalent_bytes"]

    def test_pool_exhaustion_preempts_and_stays_exact(self, llama):
        cfg, params = llama
        sp = SamplingParams(temperature=0.6, top_k=10, max_tokens=8,
                            seed=3)
        lens = (4, 9, 6, 13)
        dense = engine.InferenceServer(cfg, params, max_len=32,
                                       max_batch=3)
        ref = dense.serve(_reqs(cfg, lens, sp))
        tiny = engine.InferenceServer(cfg, params, max_len=32, max_batch=3,
                                      cache="paged", page_size=4, pages=7)
        out = tiny.serve(_reqs(cfg, lens, sp))
        assert tiny.stats["preemptions"] > 0
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref[i], out[i])
        assert tiny.stats["memory"]["pages_in_use"] == 0

    def test_page_size_one_under_preemption(self, llama):
        """Worst case for append idempotency: every token is a page
        boundary, and the engine's preempt-and-retry loop must not
        double-advance a handle whose append raised PoolExhausted."""
        cfg, params = llama
        sp = SamplingParams(max_tokens=6)
        lens = (4, 7, 5)
        dense = engine.InferenceServer(cfg, params, max_len=16,
                                       max_batch=2)
        ref = dense.serve(_reqs(cfg, lens, sp, seed=7))
        tiny = engine.InferenceServer(cfg, params, max_len=16, max_batch=2,
                                      cache="paged", page_size=1,
                                      pages=14)
        out = tiny.serve(_reqs(cfg, lens, sp, seed=7))
        assert tiny.stats["preemptions"] > 0
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref[i], out[i])
        assert tiny.stats["memory"]["pages_in_use"] == 0

    def test_infeasible_request_rejected_up_front(self, llama):
        cfg, params = llama
        srv = engine.InferenceServer(cfg, params, max_len=32, max_batch=2,
                                     cache="paged", page_size=4, pages=3)
        sp = SamplingParams(max_tokens=12)
        with pytest.raises(ValueError, match="never"):
            srv.serve(_reqs(cfg, (16,), sp))

    def test_hybrid_arch_kv_pages_plus_ssm_slots(self):
        """jamba: attention layers page, mamba layers use the slot pool,
        prefill stays exact-length (padding would pollute the SSM state)."""
        cfg = registry.get("jamba-1.5-large-398b-smoke")
        params = lm.init_params(cfg, jax.random.key(0))
        dense = engine.InferenceServer(cfg, params, max_len=48,
                                       max_batch=2)
        paged = engine.InferenceServer(cfg, params, max_len=48,
                                       max_batch=2, cache="paged",
                                       page_size=8, pages=8)
        # hybrid: pool-direct prefill, but at EXACT length (q-chunk
        # padding would pollute the SSM state)
        assert paged._paged_kv and paged._has_ssm
        sp = SamplingParams(max_tokens=4)
        ref = dense.serve(_reqs(cfg, (7, 12), sp, seed=3))
        out = paged.serve(_reqs(cfg, (7, 12), sp, seed=3))
        for i in range(2):
            np.testing.assert_array_equal(ref[i], out[i])
        mem = paged.stats["memory"]
        assert mem["ssm_slot_bytes"] > 0 and mem["peak_pages_in_use"] > 0

    def test_ssm_arch_on_paged_backend(self):
        cfg = registry.get("mamba2-780m-smoke")
        params = lm.init_params(cfg, jax.random.key(1))
        dense = engine.InferenceServer(cfg, params, max_len=48,
                                       max_batch=2)
        paged = engine.InferenceServer(cfg, params, max_len=48,
                                       max_batch=2, cache="paged",
                                       page_size=8)
        sp = SamplingParams(max_tokens=4)
        ref = dense.serve(_reqs(cfg, (33, 17), sp, seed=2))
        out = paged.serve(_reqs(cfg, (33, 17), sp, seed=2))
        for i in range(2):
            np.testing.assert_array_equal(ref[i], out[i])


class TestLayerAddressedPool:
    """Each KV pool holds every layer's pages, ``(nsb, n_pages + 1,
    page_size, hkv * hd)``, and the stack runners address layer ``j``'s
    pages in place.  After a decode or prefill step each layer's pages,
    read back through the tables, hold exactly the dense cache of that
    layer, and no page that no live slot owns has changed in any layer:
    writing layer ``j`` never changes layer ``k``'s pages."""

    PS, N_PB, N_PAGES = 4, 4, 12

    def _case(self, cfg, b, seed):
        rng = np.random.default_rng(seed)
        caches = lm.init_paged_caches(cfg, b, self.PS, self.N_PAGES)
        caches = jax.tree.map(
            lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
            caches)
        tables = np.zeros((b, self.N_PB), np.int32)
        tables.flat[:] = rng.permutation(
            np.arange(1, self.N_PAGES + 1))[:tables.size]
        return caches, tables

    @staticmethod
    def _rows(pool, tables, cfg):
        """(nsb, B, P * page_size, hkv, hd) rows of every layer."""
        pool = np.asarray(pool)
        return pool[:, tables].reshape(pool.shape[0], tables.shape[0], -1,
                                       cfg.hkv_eff, cfg.head_dim)

    def _check_pages(self, cfg, old, new, want, tables, live):
        owned = np.zeros(self.N_PAGES + 1, bool)
        owned[tables[list(live)]] = True
        owned[0] = True                     # the null page: garbage
        for k in ("k", "v"):
            got = np.asarray(new["l0"]["kv"][k])
            assert got.shape == np.asarray(old["l0"]["kv"][k]).shape
            np.testing.assert_array_equal(
                got[:, ~owned], np.asarray(old["l0"]["kv"][k])[:, ~owned])
            rows = self._rows(got, tables, cfg)
            for b, n in live.items():
                np.testing.assert_array_equal(
                    rows[:, b, :n], np.asarray(want["l0"]["kv"][k])[:, b, :n])

    @staticmethod
    def _params(params, runner):
        if runner == "scanned":
            return params
        nsb = jax.tree.leaves(params["blocks"])[0].shape[0]
        return dict(params, blocks=tuple(
            jax.tree.map(lambda a, j=j: a[j], params["blocks"])
            for j in range(nsb)))

    @pytest.mark.parametrize("runner", ["scanned", "unrolled"])
    def test_decode_writes_stay_in_own_layer_pages(self, llama, runner):
        cfg, params = llama
        params = self._params(params, runner)
        assert lm.n_superblocks(cfg) >= 2
        caches, tables = self._case(cfg, 3, seed=21)
        tables[1] = 0                                  # an inactive slot
        pos = np.asarray([6, 0, 13], np.int32)
        live = {0: 7, 2: 14}                           # slot: tokens held
        dense = {"l0": {"kv": {k: jnp.asarray(self._rows(
            caches["l0"]["kv"][k], tables, cfg))
            for k in ("k", "v")}}}
        tok = {"tokens": jnp.asarray([[5], [9], [17]], jnp.int32)}
        lg_d, want = jax.jit(functools.partial(lm.decode_step, cfg))(
            params, tok, dense, jnp.asarray(pos))
        lg_p, got = jax.jit(functools.partial(lm.decode_step, cfg))(
            params, tok, caches, jnp.asarray(pos),
            tables=jnp.asarray(tables))
        np.testing.assert_array_equal(np.asarray(lg_p)[[0, 2]],
                                      np.asarray(lg_d)[[0, 2]])
        self._check_pages(cfg, caches, got, want, tables, live)

    @pytest.mark.parametrize("runner", ["scanned", "unrolled"])
    def test_prefill_writes_stay_in_own_layer_pages(self, llama, runner):
        cfg, params = llama
        params = self._params(params, runner)
        caches, tables = self._case(cfg, 1, seed=22)
        n, s = 13, self.PS * self.N_PB
        toks = np.zeros((1, s), np.int32)
        toks[0, :n] = _prompts(cfg, (n,), seed=4)[0]
        lg_d, want = jax.jit(functools.partial(
            lm.forward, cfg, mode="prefill", logits_mode="last",
            last_pos=n - 1))(params, {"tokens": jnp.asarray(toks)})
        lg_p, got = jax.jit(functools.partial(
            lm.forward, cfg, mode="prefill", logits_mode="last",
            last_pos=n - 1))(params, {"tokens": jnp.asarray(toks)},
                             caches=caches, pos=jnp.asarray([n]),
                             tables=jnp.asarray(tables))
        np.testing.assert_array_equal(np.asarray(lg_p), np.asarray(lg_d))
        self._check_pages(cfg, caches, got, want, tables, {0: n})


class TestPagedKernelParity:
    """The Pallas paged-attention kernel (interpret mode on CPU) must
    reproduce the dense backend's token streams exactly -- the PR 3
    invariant survives the in-place pool read."""

    def test_kernel_impl_matches_dense_tokens(self, llama):
        cfg, params = llama
        sp_greedy = SamplingParams(max_tokens=4)
        sp_seeded = SamplingParams(temperature=0.8, top_k=7, max_tokens=4,
                                   seed=3)
        lens = (5, 11)
        dense = engine.InferenceServer(cfg, params, max_len=16,
                                       max_batch=2)
        ref_g = dense.serve(_reqs(cfg, lens, sp_greedy, seed=1))
        ref_s = dense.serve(_reqs(cfg, lens, sp_seeded, seed=1))
        with paged_ops.force_impl("kernel"):
            # fresh server: its decode step traces (and therefore bakes
            # in the forced impl) on first use inside this block
            paged = engine.InferenceServer(cfg, params, max_len=16,
                                           max_batch=2, cache="paged",
                                           page_size=8)
            out_g = paged.serve(_reqs(cfg, lens, sp_greedy, seed=1))
            out_s = paged.serve(_reqs(cfg, lens, sp_seeded, seed=1))
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref_g[i], out_g[i])
            np.testing.assert_array_equal(ref_s[i], out_s[i])

    def test_mirror_ref_impl_matches_dense_tokens(self, llama):
        cfg, params = llama
        sp = SamplingParams(max_tokens=4)
        lens = (5, 11)
        dense = engine.InferenceServer(cfg, params, max_len=16,
                                       max_batch=2)
        ref = dense.serve(_reqs(cfg, lens, sp, seed=1))
        with paged_ops.force_impl("ref"):
            paged = engine.InferenceServer(cfg, params, max_len=16,
                                           max_batch=2, cache="paged",
                                           page_size=8)
            out = paged.serve(_reqs(cfg, lens, sp, seed=1))
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref[i], out[i])


class TestPagedPrefillConformance:
    """PR 10: admission-time prefill runs the q-chunked paged kernel
    straight over the page pool (no dense scatter round-trip).  The
    dense-vs-paged token-equality invariant must survive it across the
    full serving matrix, for every prefill impl in the fallback ladder.
    """

    @pytest.mark.parametrize("impl", ["kernel", "view"])
    def test_float_solo_batched_streaming(self, llama, impl):
        """solo == batched == streaming-arrivals == dense, with prompt
        lengths hitting an exact page multiple (16), a multiple-minus-1
        (15), an odd length and a single token."""
        cfg, params = llama
        sp = SamplingParams(max_tokens=5)
        lens = (13, 16, 1, 15)
        dense = engine.InferenceServer(cfg, params, max_len=48, max_batch=2)
        ref_b = dense.serve(_reqs(cfg, lens, sp, seed=9))
        ref_s = dense.serve([_reqs(cfg, lens, sp, seed=9)[1]])
        with paged_ops.force_impl(impl):
            paged = engine.InferenceServer(cfg, params, max_len=48,
                                           max_batch=2, cache="paged",
                                           page_size=8, pages=12)
            out_b = paged.serve(_reqs(cfg, lens, sp, seed=9))
            out_s = paged.serve([_reqs(cfg, lens, sp, seed=9)[1]])
            out_g = paged.serve(_reqs(cfg, lens, sp, gap=2, seed=9))
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref_b[i], out_b[i])
            np.testing.assert_array_equal(ref_b[i], out_g[i])
        np.testing.assert_array_equal(ref_s[1], out_s[1])
        assert paged.stats["memory"]["pages_in_use"] == 0

    def test_quantized_preempted_reprefill_kernel_impl(self, llama):
        """Plan-quantized weights + a pool small enough to preempt: the
        resumed requests re-prefill prompt+generated through the paged
        KERNEL and every stream stays byte-identical to dense."""
        cfg, params = llama
        plan = engine.synthetic_plan(cfg, params, bits=None, seed=0)
        sp = SamplingParams(temperature=0.6, top_k=10, max_tokens=8,
                            seed=3)
        lens = (4, 9, 6, 13)
        dense = engine.InferenceServer(cfg, params, plan=plan, max_len=32,
                                       max_batch=3)
        ref = dense.serve(_reqs(cfg, lens, sp, seed=5))
        with paged_ops.force_impl("kernel"):
            tiny = engine.InferenceServer(cfg, params, plan=plan,
                                          max_len=32, max_batch=3,
                                          cache="paged", page_size=4,
                                          pages=7)
            out = tiny.serve(_reqs(cfg, lens, sp, seed=5))
        assert tiny.stats["preemptions"] > 0     # re-prefill exercised
        for i in range(len(lens)):
            np.testing.assert_array_equal(ref[i], out[i])
        assert tiny.stats["memory"]["pages_in_use"] == 0

    def test_page_boundary_prompts_same_footprint(self, llama):
        """Stale-bucket hazard regression: prompts of exactly
        page_size*k and page_size*k - 1 tokens land in the same
        written-page footprint -- identical memory_report() page counts.
        (The old padded bucketed prefill scattered the padded length, so
        a boundary-straddling bucket could touch one page more than the
        admission priced.)"""
        cfg, params = llama
        sp = SamplingParams(max_tokens=3)
        reports = {}
        for n in (16, 15):                       # page_size*2, *2 - 1
            paged = engine.InferenceServer(cfg, params, max_len=48,
                                           max_batch=2, cache="paged",
                                           page_size=8, pages=10)
            dense = engine.InferenceServer(cfg, params, max_len=48,
                                           max_batch=2)
            ref = dense.serve(_reqs(cfg, (n,), sp, seed=1))
            out = paged.serve(_reqs(cfg, (n,), sp, seed=1))
            np.testing.assert_array_equal(ref[0], out[0])
            reports[n] = paged.stats["memory"]
        for key in ("pages_in_use", "peak_pages_in_use"):
            assert reports[16][key] == reports[15][key], key
        # prompt+decode spans positions 0..18 -> exactly 3 pages peak
        assert reports[16]["peak_pages_in_use"] == 3
        assert reports[16]["pages_in_use"] == 0

    def test_one_bounded_upload_per_admission_no_retrace(self, llama):
        """Admission uploads exactly ONE table row (alloc's incremental
        patch); the paged prefill itself slices the slot's row on device
        and performs no further host->device table traffic.  A warm
        second session must not re-trace any cache updater."""
        cfg, params = llama
        sp = SamplingParams(max_tokens=4)
        lens = (13, 9, 13, 9)
        paged = engine.InferenceServer(cfg, params, max_len=48,
                                       max_batch=2, cache="paged",
                                       page_size=8, pages=12)
        paged.serve(_reqs(cfg, lens, sp, seed=3))
        mem = paged.stats["memory"]
        assert paged.stats["preemptions"] == 0   # pool is ample
        assert mem["table_host_uploads"] == paged.stats["admitted"] == 4
        # warm server, same lengths: no new traces of the jitted table
        # updaters or the prefill/insert path
        traces = dict(cache_mod.TRACE_COUNTS)
        paged.serve(_reqs(cfg, lens, sp, seed=4))
        assert dict(cache_mod.TRACE_COUNTS) == traces
        assert paged.stats["memory"]["table_host_uploads"] == 4


class TestDeviceTables:
    """The block tables live on device across steps; decode must not
    re-upload or re-trace anything per step."""

    def test_no_per_step_host_sync(self, llama):
        cfg, _ = llama
        b = cache_mod.PagedCache(cfg, max_batch=2, max_len=32,
                                 page_size=8, n_pages=6)
        h = b.alloc(uid=0, slot=0, n_prompt=5)
        uploads0 = b.table_host_uploads
        t0 = b.device_tables()
        # steady-state decode inside a page: the SAME device array is
        # handed out every step -- no upload, no update, no new trace
        traces0 = dict(cache_mod.TRACE_COUNTS)
        for _ in range(2):
            b.append(h)                      # pos 6, 7: within page 0
            assert b.device_tables() is t0
        assert b.table_host_uploads == uploads0
        assert dict(cache_mod.TRACE_COUNTS) == traces0
        # page-boundary crossing patches ONE entry via the jitted
        # updater (no full-table host upload)
        b.append(h)                          # next write pos 8: new page
        t1 = b.device_tables()
        assert t1 is not t0
        assert b.table_host_uploads == uploads0
        np.testing.assert_array_equal(np.asarray(t1), b._table)
        # a second crossing must reuse the compiled updater (no retrace)
        entry_traces = cache_mod.TRACE_COUNTS["table_set_entry"]
        for _ in range(8):
            b.append(h)                      # crosses into page 2 at 16
        assert cache_mod.TRACE_COUNTS["table_set_entry"] == entry_traces
        np.testing.assert_array_equal(np.asarray(b.device_tables()),
                                      b._table)
        b.free(h)
        np.testing.assert_array_equal(np.asarray(b.device_tables()), 0)

    def test_tables_track_alloc_and_free(self, llama):
        cfg, _ = llama
        b = cache_mod.PagedCache(cfg, max_batch=3, max_len=32,
                                 page_size=8, n_pages=9)
        h0 = b.alloc(uid=0, slot=0, n_prompt=17)
        h1 = b.alloc(uid=1, slot=2, n_prompt=3)
        np.testing.assert_array_equal(np.asarray(b.device_tables()),
                                      b._table)
        b.free(h0)
        np.testing.assert_array_equal(np.asarray(b.device_tables()),
                                      b._table)
        assert np.asarray(b.device_tables())[0].sum() == 0
        assert np.asarray(b.device_tables())[2].sum() > 0
        b.free(h1)


# ---------------------------------------------------------------------------
# on-device sampling vs. the host fallback
# ---------------------------------------------------------------------------

class TestOnDeviceSampling:
    def test_greedy_device_equals_host(self, llama):
        cfg, params = llama
        dev = engine.InferenceServer(cfg, params, max_len=48, max_batch=2)
        host = engine.InferenceServer(cfg, params, max_len=48, max_batch=2,
                                      sample_on_device=False)
        sp = SamplingParams(max_tokens=6)
        a = dev.serve(_reqs(cfg, (5, 9), sp, seed=4))
        b = host.serve(_reqs(cfg, (5, 9), sp, seed=4))
        for i in range(2):
            np.testing.assert_array_equal(a[i], b[i])

    def test_host_fallback_keeps_batched_solo_parity(self, llama):
        cfg, params = llama
        host = engine.InferenceServer(cfg, params, max_len=48, max_batch=2,
                                      sample_on_device=False)
        sp = SamplingParams(temperature=0.9, top_k=8, max_tokens=5,
                            seed=5)
        reqs = _reqs(cfg, (6, 6, 6), sp, seed=5)
        both = host.serve(reqs)
        solo = host.serve([reqs[1]])
        np.testing.assert_array_equal(both[1], solo[1])

    def test_device_sampling_respects_top_k_and_seed(self, llama):
        cfg, params = llama
        srv = engine.InferenceServer(cfg, params, max_len=48, max_batch=2)
        sp1 = SamplingParams(temperature=1.0, top_k=2, max_tokens=8,
                             seed=0)
        sp2 = SamplingParams(temperature=1.0, top_k=2, max_tokens=8,
                             seed=9)
        r1 = srv.serve(_reqs(cfg, (6,), sp1, seed=6))
        r1b = srv.serve(_reqs(cfg, (6,), sp1, seed=6))
        r2 = srv.serve(_reqs(cfg, (6,), sp2, seed=6))
        np.testing.assert_array_equal(r1[0], r1b[0])   # deterministic
        assert not np.array_equal(r1[0], r2[0])        # seed matters

    def test_top_k_sort_skip_is_exact(self):
        """need_top_k=False (no row truncates) must draw the identical
        tokens as the sorting path: pure-temperature and top_k >= vocab
        rows keep the whole support either way."""
        rng = np.random.default_rng(0)
        v = 64
        logits = jnp.asarray(rng.normal(size=(3, v)).astype(np.float32))
        temps = jnp.asarray([0.9, 0.0, 1.7], jnp.float32)
        seeds = jnp.asarray([1, 2, 3], jnp.int32)
        uids = jnp.asarray([10, 11, 12], jnp.int32)
        tidx = jnp.asarray([0, 5, 9], jnp.int32)
        for topks in ([0, 0, 0], [v, 0, v + 7]):
            tk = jnp.asarray(topks, jnp.int32)
            with_sort = sample_tokens_device(logits, temps, tk, seeds,
                                             uids, tidx, need_top_k=True)
            skipped = sample_tokens_device(logits, temps, tk, seeds,
                                           uids, tidx, need_top_k=False)
            np.testing.assert_array_equal(np.asarray(with_sort),
                                          np.asarray(skipped))

    def test_pure_temperature_serve_uses_skip_path(self, llama):
        """End-to-end: a pure-temperature batch (top_k=0) is served and
        stays deterministic; a later truncating batch on the same server
        still truncates (the static flag recompiles, not corrupts)."""
        cfg, params = llama
        srv = engine.InferenceServer(cfg, params, max_len=48, max_batch=2)
        sp = SamplingParams(temperature=1.1, max_tokens=6, seed=2)
        a = srv.serve(_reqs(cfg, (6, 9), sp, seed=8))
        b = srv.serve(_reqs(cfg, (6, 9), sp, seed=8))
        for i in range(2):
            np.testing.assert_array_equal(a[i], b[i])
        spk = SamplingParams(temperature=1.1, top_k=2, max_tokens=6,
                             seed=2)
        c = srv.serve(_reqs(cfg, (6, 9), spk, seed=8))
        assert not all(np.array_equal(a[i], c[i]) for i in range(2))
