"""Plan-driven serving stack tests: SamplingParams, the continuous-
batching scheduler, PackedLinear vs. the per-layer mixed_precision_matmul
oracle, float/quantized InferenceServer parity (batched == one-by-one ==
streaming), plan round-trips into quantized decode, fully-pruned layers,
and the PeriodicEval assignment cache."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import proptest as pt
from repro import obs
from repro.chaos import inject as chaos_inject
from repro.configs import registry
from repro.launch import steps
from repro.models import lm
from repro.nn import quantized as nnq
from repro.serve import cache as cache_mod
from repro.serve import engine
from repro.serve.sampling import (SamplingParams, make_rng, sample_token,
                                  sample_tokens_device)
from repro.serve.scheduler import Request, Scheduler


@pytest.fixture(scope="module")
def llama():
    cfg = registry.get("llama3.2-1b-smoke")
    return cfg, lm.init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def llama_plan(llama):
    """A deterministic 'searched' plan: gamma-carrying params with
    randomized selection logits, discretized through lm.extract_plan."""
    cfg, _ = llama
    params = lm.init_params(cfg, jax.random.key(0), mps_on=True)
    key = jax.random.key(7)

    def randomize(node):
        nonlocal key
        if isinstance(node, dict):
            if "gamma" in node:
                key, sub = jax.random.split(key)
                node["gamma"] = jax.random.normal(
                    sub, node["gamma"].shape) * 3.0
            for v in node.values():
                randomize(v)

    params = jax.tree.map(lambda x: x, params)
    randomize(params)
    return params, lm.extract_plan(cfg, params)


def _prompts(cfg, n, s0, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, size=(n, s0)).astype(np.int32)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class TestSampling:
    def test_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(temperature=-1.0)
        with pytest.raises(ValueError):
            SamplingParams(top_k=-1)
        with pytest.raises(ValueError):
            SamplingParams(max_tokens=0)

    def test_greedy_is_argmax(self):
        logits = np.asarray([0.1, 3.0, -2.0, 1.5])
        sp = SamplingParams()
        assert sp.greedy
        assert sample_token(logits, sp, make_rng(sp, 0)) == 1

    def test_seeded_sampling_deterministic(self):
        logits = np.random.default_rng(0).normal(size=64)
        sp = SamplingParams(temperature=0.9, top_k=8, seed=3)
        draws1 = [sample_token(logits, sp, make_rng(sp, 5))
                  for _ in range(4)]
        # a fresh generator from the same (seed, uid) replays the stream
        rng = make_rng(sp, 5)
        draws2 = [sample_token(logits, sp, rng) for _ in range(4)]
        assert [draws1[0]] * 4 == draws1          # same rng state each call
        rng = make_rng(sp, 5)
        seq = [sample_token(logits, sp, rng) for _ in range(4)]
        rng = make_rng(sp, 5)
        assert seq == [sample_token(logits, sp, rng) for _ in range(4)]
        assert draws2[0] == seq[0]

    def test_top_k_restricts_support(self):
        logits = np.asarray([10.0, 9.0, -50.0, -50.0])
        sp = SamplingParams(temperature=1.0, top_k=2, seed=0)
        rng = make_rng(sp, 0)
        draws = {sample_token(logits, sp, rng) for _ in range(50)}
        assert draws <= {0, 1}


# ---------------------------------------------------------------------------
# scheduler (pure bookkeeping)
# ---------------------------------------------------------------------------

class TestScheduler:
    def _req(self, uid, arrival=0, s0=4, max_tokens=4):
        return Request(uid=uid, prompt=np.arange(s0, dtype=np.int32),
                       sampling=SamplingParams(max_tokens=max_tokens),
                       arrival=arrival)

    def test_fifo_admission_and_slot_reuse(self):
        sched = Scheduler(max_batch=2, max_len=32)
        for uid in range(3):
            sched.submit(self._req(uid))
        e0, s0 = sched.pop_admissible(0)
        sched.activate(s0, _dummy_state(e0, s0))
        e1, s1 = sched.pop_admissible(0)
        sched.activate(s1, _dummy_state(e1, s1))
        assert (e0.request.uid, e1.request.uid) == (0, 1)
        assert sched.pop_admissible(0) is None     # slots full
        sched.complete(s0)
        e2, s2 = sched.pop_admissible(0)
        assert e2.request.uid == 2 and s2 == s0    # freed slot reused
        assert sched.has_work

    def test_arrival_gating(self):
        sched = Scheduler(max_batch=2, max_len=32)
        sched.submit(self._req(0, arrival=5))
        assert sched.pop_admissible(4) is None
        assert sched.next_arrival == 5
        entry, _ = sched.pop_admissible(5)
        assert entry.request.uid == 0

    def test_validation(self):
        sched = Scheduler(max_batch=1, max_len=8)
        with pytest.raises(ValueError):     # prompt + max_tokens > max_len
            sched.submit(self._req(0, s0=6, max_tokens=4))
        sched.submit(self._req(1))
        with pytest.raises(ValueError):     # duplicate uid
            sched.submit(self._req(1))

    def test_cancel_pending_and_active(self):
        sched = Scheduler(max_batch=1, max_len=32)
        for uid in range(3):
            sched.submit(self._req(uid))
        e0, s0 = sched.pop_admissible(0)
        sched.activate(s0, _dummy_state(e0, s0))
        where, state = sched.cancel(0, kind="timeout")
        assert where == "active" and state.request.uid == 0
        assert sched.slots[s0] is None       # slot freed immediately
        where, entry = sched.cancel(2)
        assert where == "pending" and entry.request.uid == 2
        assert sched.cancel(0) is None       # no longer live
        assert sched.cancel(99) is None      # never existed
        with pytest.raises(ValueError):
            sched.cancel(1, kind="vanished")
        # uid 1 is the only survivor and admits next
        entry, _ = sched.pop_admissible(0)
        assert entry.request.uid == 1

    def test_load_counts_remaining_tokens(self):
        sched = Scheduler(max_batch=2, max_len=32)
        sched.submit(self._req(0, max_tokens=4))
        sched.submit(self._req(1, max_tokens=6))
        e0, s0 = sched.pop_admissible(0)
        st = _dummy_state(e0, s0)
        sched.activate(s0, st)
        load = sched.load()
        assert load == {"queued": 1, "active": 1,
                        "queued_tokens": 6, "active_tokens": 4}
        st.remaining = 1                     # 3 tokens decoded
        sched.preempt(s0)
        load = sched.load()                  # resume carries remaining=1
        assert load == {"queued": 2, "active": 0,
                        "queued_tokens": 7, "active_tokens": 0}

    @pt.given(seed=pt.integers(0, 10**9), max_batch=pt.integers(1, 3),
              n_req=pt.integers(4, 9))
    def test_fcfs_property_under_mixed_ops(self, seed, max_batch, n_req):
        """Strict FCFS survives any interleaving of admissions,
        completions, preemptions, cancellations, timeouts and replica
        crash/recover cycles: each admission must pop the model queue's
        exact head (preempted requests re-admit from the FRONT, fresh
        ones in submit order, cancelled ones never), a crash that
        cancels every live request and front-re-enqueues them in
        reverse seniority (the fleet's failover path) restores the
        exact pre-crash order, and load() mirrors the model throughout.
        The prefill_admit op models the engine's memory-gated paged
        admission: can_admit is consulted on the HEAD only, a blocked
        head blocks the whole queue (no skip-ahead), and an admitted
        entry's tokens() is exactly what prefill re-runs.
        Complements the trace-replay FRONT-order check in test_obs."""
        rng = np.random.default_rng(seed)
        sched = Scheduler(max_batch=max_batch, max_len=32)
        for uid in range(n_req):
            sched.submit(self._req(uid))
        queue = [uid for uid in range(n_req)]     # model: exact order
        active: dict[int, object] = {}            # slot -> uid
        done = set()
        admit_seq = 0                             # admission order
        for _ in range(60):
            if not queue and not active:
                break
            op = rng.choice(["admit", "prefill_admit", "complete",
                             "preempt", "cancel", "crash"])
            if op == "admit":
                res = sched.pop_admissible(0)
                if len(active) == max_batch or not queue:
                    assert res is None
                    continue
                entry, slot = res
                assert entry.request.uid == queue[0], \
                    f"admitted {entry.request.uid}, head was {queue}"
                queue.pop(0)
                st = _dummy_state(entry, slot)
                st.order = admit_seq
                admit_seq += 1
                sched.activate(slot, st)
                active[slot] = st
            elif op == "prefill_admit":
                # the engine's paged-prefill admission: the backend's
                # memory gate sees the head only -- blocked head, blocked
                # queue (strict FCFS, no probe of later entries)
                blocked = bool(rng.integers(0, 2))
                probed = []

                def gate(entry, probed=probed, blocked=blocked):
                    probed.append(entry.request.uid)
                    return not blocked

                res = sched.pop_admissible(0, can_admit=gate)
                if len(active) == max_batch or not queue:
                    assert res is None
                    continue
                assert probed == [queue[0]]      # gate saw only the head
                if blocked:
                    assert res is None           # head-of-line blocking
                    continue
                entry, slot = res
                assert entry.request.uid == queue[0]
                # a resumed entry re-prefills prompt + generated stream
                exp = entry.request.prompt.size + (
                    len(entry.resume.out) if entry.resume else 0)
                assert entry.tokens().size == exp
                queue.pop(0)
                st = _dummy_state(entry, slot)
                st.order = admit_seq
                admit_seq += 1
                sched.activate(slot, st)
                active[slot] = st
            elif op == "crash" and (queue or active):
                # seniority: actives by admission order, then queue
                model_live = ([st.request.uid for st in
                               sorted(active.values(),
                                      key=lambda s: s.order)] + queue)
                assert sched.live_uids() == model_live
                for uid in model_live:
                    assert sched.cancel(uid, kind="crashed") is not None
                # zero leaks: every slot must be released, else its
                # cache handle (freed keyed on slot state) would strand
                assert sched.active == []
                active.clear()
                # recompute-style recovery: fresh requests, re-enqueued
                # to the FRONT in reverse seniority -> original order
                for uid in reversed(model_live):
                    sched.submit(self._req(uid), front=True)
                queue = list(model_live)
                assert [e.request.uid for e in sched.pending] == queue
            elif op == "complete" and active:
                slot = int(rng.choice(list(active)))
                done.add(active.pop(slot).request.uid)
                sched.complete(slot)
            elif op == "preempt" and active:
                slot = int(rng.choice(list(active)))
                st = active.pop(slot)
                assert sched.preempt(slot) is st
                queue.insert(0, st.request.uid)   # FRONT re-admission
            elif op == "cancel" and (queue or active):
                live = queue + [st.request.uid for st in active.values()]
                uid = int(rng.choice(live))
                kind = str(rng.choice(["cancelled", "timeout"]))
                where, _ = sched.cancel(uid, kind=kind)
                if uid in queue:
                    assert where == "pending"
                    queue.remove(uid)
                else:
                    assert where == "active"
                    active = {s: st for s, st in active.items()
                              if st.request.uid != uid}
            load = sched.load()
            assert load["queued"] == len(queue)
            assert load["active"] == len(active)
        assert set(sched.finished) == done


def _dummy_state(entry, slot):
    from repro.serve.scheduler import SlotState
    req = entry.request
    return SlotState(request=req, slot=slot, pos=req.prompt.size,
                     remaining=req.sampling.max_tokens, out=[],
                     rng=make_rng(req.sampling, req.uid))


# ---------------------------------------------------------------------------
# PackedLinear vs. the per-layer oracle
# ---------------------------------------------------------------------------

class TestPackedLinear:
    def test_matches_mixed_precision_matmul_oracle(self):
        """The in-forward PackedLinear path must serve exactly the packed
        groups the per-layer export produces: bitwise-equal to scattering
        engine.mixed_precision_matmul output back to channel order."""
        rng = np.random.default_rng(0)
        k, n = 32, 48
        w = rng.normal(size=(k, n)).astype(np.float32) * 0.2
        bits = rng.choice([0, 2, 4, 8], size=n, p=[0.2, 0.2, 0.3, 0.3])
        pl = nnq.PackedLinear.from_dense(w, bits)
        packed, perm, kept = engine.export_mixed_precision_layer(w.T, bits)
        x = jnp.asarray(rng.normal(size=(5, k)).astype(np.float32))
        y_pl = pl(x)
        y_oracle = engine.mixed_precision_matmul(x, packed)   # (5, kept)
        scatter = np.zeros((5, n), np.float32)
        scatter[:, np.asarray(perm)[:kept]] = np.asarray(y_oracle)
        np.testing.assert_array_equal(np.asarray(y_pl), scatter)
        # pruned channels are exactly zero
        assert np.all(np.asarray(y_pl)[:, bits == 0] == 0.0)

    def test_per_row_activation_scales_are_batch_invariant(self):
        rng = np.random.default_rng(1)
        w = rng.normal(size=(16, 8)).astype(np.float32)
        bits = np.full(8, 4, np.int64)
        pl = nnq.PackedLinear.from_dense(w, bits)
        x = rng.normal(size=(4, 16)).astype(np.float32)
        # row 2 served alone == row 2 served in the batch (incl. a row
        # with a much larger magnitude that would shift a per-tensor scale)
        x[0] *= 100.0
        full = np.asarray(pl(jnp.asarray(x)))
        solo = np.asarray(pl(jnp.asarray(x[2:3])))
        np.testing.assert_array_equal(full[2:3], solo)

    def test_fully_pruned_layer(self):
        w = np.ones((8, 6), np.float32)
        bits = np.zeros(6, np.int64)
        packed, perm, kept = engine.export_mixed_precision_layer(w.T, bits)
        assert packed == [] and kept == 0
        y = engine.mixed_precision_matmul(jnp.ones((3, 8)), packed)
        assert y.shape == (3, 0)                   # well-defined zero-width
        pl = nnq.PackedLinear.from_dense(w, bits)
        out = pl(jnp.ones((2, 3, 8)))
        assert out.shape == (2, 3, 6)
        assert np.all(np.asarray(out) == 0.0)

    def test_quantized_linear_apply_empty(self):
        from repro.kernels.quant_matmul import ops as qops
        y = qops.quantized_linear_apply(jnp.ones((4, 8)), [])
        assert y.shape == (4, 0)

    def test_packed_linear_is_a_pytree(self):
        w = np.random.default_rng(2).normal(size=(8, 8)).astype(np.float32)
        pl = nnq.PackedLinear.from_dense(w, np.asarray([0, 2, 2, 4, 4, 8,
                                                        8, 8]))
        leaves, treedef = jax.tree_util.tree_flatten(pl)
        pl2 = jax.tree_util.tree_unflatten(treedef, leaves)
        x = jnp.ones((2, 8))
        np.testing.assert_array_equal(np.asarray(pl(x)),
                                      np.asarray(pl2(x)))
        y = jax.jit(lambda m, v: m(v))(pl, x)      # crosses a jit boundary
        np.testing.assert_array_equal(np.asarray(y), np.asarray(pl(x)))


# ---------------------------------------------------------------------------
# InferenceServer: float continuous batching
# ---------------------------------------------------------------------------

class TestContinuousBatching:
    def test_batched_equals_one_by_one(self, llama):
        cfg, params = llama
        server = engine.InferenceServer(cfg, params, max_len=48,
                                        max_batch=2)
        prompts = _prompts(cfg, 3, 6)
        sp = SamplingParams(temperature=0.8, top_k=16, max_tokens=6,
                            seed=11)
        reqs = [Request(uid=i, prompt=prompts[i], sampling=sp)
                for i in range(3)]
        together = server.serve(reqs)       # 3 requests over 2 slots
        assert server.stats["admitted"] == 3
        for r in reqs:
            solo = server.serve([Request(uid=r.uid, prompt=r.prompt,
                                         sampling=sp)])
            np.testing.assert_array_equal(together[r.uid], solo[r.uid])

    def test_streaming_arrivals_match_all_at_once(self, llama):
        cfg, params = llama
        server = engine.InferenceServer(cfg, params, max_len=48,
                                        max_batch=4)
        prompts = _prompts(cfg, 3, 5, seed=4)
        sp = SamplingParams(max_tokens=5)   # greedy
        batch = server.serve([Request(uid=i, prompt=prompts[i],
                                      sampling=sp) for i in range(3)])
        stream = server.serve([Request(uid=i, prompt=prompts[i],
                                       sampling=sp, arrival=3 * i)
                               for i in range(3)])
        for i in range(3):
            np.testing.assert_array_equal(batch[i], stream[i])

    def test_variable_prompt_lengths_and_budgets(self, llama):
        cfg, params = llama
        server = engine.InferenceServer(cfg, params, max_len=48,
                                        max_batch=2)
        reqs = [Request(uid=0, prompt=_prompts(cfg, 1, 4)[0],
                        sampling=SamplingParams(max_tokens=3)),
                Request(uid=1, prompt=_prompts(cfg, 1, 9, seed=1)[0],
                        sampling=SamplingParams(max_tokens=7)),
                Request(uid=2, prompt=_prompts(cfg, 1, 6, seed=2)[0],
                        sampling=SamplingParams(max_tokens=1))]
        out = server.serve(reqs)
        assert {len(out[i]) for i in range(3)} == {3, 7, 1}
        assert all(out[i].max() < cfg.vocab for i in range(3))

    def test_generate_matches_serve_and_is_deterministic(self, llama):
        cfg, params = llama
        server = engine.InferenceServer(cfg, params, max_len=48,
                                        max_batch=4)
        prompts = _prompts(cfg, 2, 5, seed=9)
        out1 = server.generate(prompts, n_tokens=4)
        out2 = server.generate(prompts, n_tokens=4)
        np.testing.assert_array_equal(out1, out2)
        assert out1.shape == (2, 4)

    def test_ssm_arch_with_awkward_prompt_length(self):
        # 33 is not a multiple of the smoke ssm_chunk (32): the prefill
        # chunking falls back to a divisor, no padding pollution
        cfg = registry.get("mamba2-780m-smoke")
        params = lm.init_params(cfg, jax.random.key(1))
        server = engine.InferenceServer(cfg, params, max_len=48,
                                        max_batch=2)
        prompts = _prompts(cfg, 2, 33, seed=2)
        sp = SamplingParams(max_tokens=4)
        both = server.serve([Request(uid=i, prompt=prompts[i], sampling=sp)
                             for i in range(2)])
        solo = server.serve([Request(uid=0, prompt=prompts[0],
                                     sampling=sp)])
        np.testing.assert_array_equal(both[0], solo[0])

    def test_rejects_unsupported_archs(self):
        cfg = registry.get("seamless-m4t-medium-smoke")
        with pytest.raises(NotImplementedError):
            engine.InferenceServer(cfg, params=None)


# ---------------------------------------------------------------------------
# request cancellation (the session API)
# ---------------------------------------------------------------------------

class TestCancellation:
    def _server(self, llama, **kw):
        cfg, params = llama
        kw.setdefault("max_len", 32)
        kw.setdefault("max_batch", 2)
        return engine.InferenceServer(cfg, params, cache="paged",
                                      page_size=4, pages=24, **kw)

    def _reqs(self, cfg, n, max_tokens=6):
        rng = np.random.default_rng(2)
        sp = SamplingParams(max_tokens=max_tokens)
        return [Request(uid=i, sampling=sp,
                        prompt=rng.integers(0, cfg.vocab, size=6)
                        .astype(np.int32))
                for i in range(n)]

    def test_cancel_queued_request(self, llama):
        cfg, _ = llama
        server = self._server(llama, max_batch=1)
        reqs = self._reqs(cfg, 2)
        server.begin(reqs)
        server.step()                       # uid 0 admitted, uid 1 queued
        toks = server.cancel(1)
        assert toks is not None and toks.size == 0   # nothing generated
        while server.has_work:
            server.step()
        out = server.end()
        assert set(out) == {0}
        assert server.stats["cancelled"] == 1
        assert server.stats["timeouts"] == 0

    def test_cancel_inflight_frees_pages_to_baseline(self, llama):
        """The leak check: cancelling an in-flight request frees its
        cache pages immediately, and the backend returns to its
        pre-admission baseline -- while the surviving request's stream
        stays byte-identical to a solo run."""
        cfg, _ = llama
        server = self._server(llama)
        reqs = self._reqs(cfg, 2)
        server.begin(reqs)
        assert server.backend.memory_report()["pages_in_use"] == 0
        server.step()                       # both admitted
        server.step()                       # a couple of decode steps
        held = server.backend.memory_report()["pages_in_use"]
        assert held > 0
        toks = server.cancel(1, reason="timeout")
        assert 0 < toks.size < reqs[1].sampling.max_tokens  # partial
        after = server.backend.memory_report()["pages_in_use"]
        assert after < held                 # pages freed right away
        while server.has_work:
            server.step()
        out = server.end()
        assert server.backend.memory_report()["pages_in_use"] == 0
        assert server.stats["timeouts"] == 1
        solo = server.serve([reqs[0]])
        np.testing.assert_array_equal(out[0], solo[0])

    def test_cancel_everything_restores_baseline_immediately(self, llama):
        cfg, _ = llama
        server = self._server(llama)
        reqs = self._reqs(cfg, 3)           # 2 active + 1 queued
        server.begin(reqs)
        server.step()
        for uid in range(3):
            server.cancel(uid)
        assert server.backend.memory_report()["pages_in_use"] == 0
        assert not server.has_work
        out = server.end()
        assert out == {}
        assert server.stats["cancelled"] == 3

    def test_cancel_validation_and_result(self, llama):
        cfg, _ = llama
        server = self._server(llama)
        reqs = self._reqs(cfg, 1, max_tokens=3)
        server.begin(reqs)
        with pytest.raises(ValueError):
            server.cancel(0, reason="evaporated")
        assert server.cancel(7) is None     # unknown uid
        assert server.result(0) is None     # not finished yet
        while server.has_work:
            server.step()
        toks = server.result(0)
        assert toks is not None and toks.size == 3
        assert server.cancel(0) is None     # finished: not cancellable
        server.end()
        with pytest.raises(RuntimeError):   # session closed
            server.cancel(0)


# ---------------------------------------------------------------------------
# plan-driven quantized decode
# ---------------------------------------------------------------------------

class TestQuantizedServing:
    def test_extract_plan_roundtrip(self, llama, llama_plan, tmp_path):
        cfg, _ = llama
        mps_params, plan = llama_plan
        loaded = type(plan).load(plan.save(str(tmp_path / "lmplan")))
        assert loaded.equals(plan)
        groups = lm.serve_weight_groups(cfg, mps_params)
        assert set(groups) == set(plan.channel_bits)
        for grp, w in groups.items():
            assert w.shape[0] == plan.channel_bits[grp].size

    def test_loaded_plan_decodes_like_the_oracle_loop(self, llama,
                                                      llama_plan,
                                                      tmp_path):
        """End-to-end acceptance: a saved+loaded plan, bound into the LM,
        serves token-for-token what a naive fused-prefill + one-token
        decode_step loop over the same per-layer packed weights produces
        -- under continuous batching with staggered arrivals."""
        cfg, params = llama
        _, plan = llama_plan
        loaded = type(plan).load(plan.save(str(tmp_path / "p")))

        max_len, n_tok = 48, 6
        prompts = _prompts(cfg, 3, 6, seed=5)
        server = engine.InferenceServer(cfg, params, plan=loaded,
                                        max_len=max_len, max_batch=2)
        sp = SamplingParams(max_tokens=n_tok)   # greedy
        served = server.serve([Request(uid=i, prompt=prompts[i],
                                       sampling=sp, arrival=2 * i)
                               for i in range(3)])

        # oracle: same plan bound per-layer, naive single-request loop
        qparams = engine.apply_plan(cfg, params, loaded)
        prefill = jax.jit(steps.make_prefill_step(cfg))
        decode = jax.jit(lambda p, t, c, pos: lm.decode_step(cfg, p, t, c,
                                                             pos))
        for i in range(3):
            caches = lm.init_caches(cfg, 1, max_len)
            logits, pc = prefill(qparams, {"tokens":
                                           jnp.asarray(prompts[i:i + 1])})
            caches = jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype), (0,) * big.ndim),
                caches, pc)
            tok = int(np.argmax(np.asarray(
                logits.astype(jnp.float32))[0, -1, :cfg.vocab]))
            out = [tok]
            pos = prompts.shape[1]
            for _ in range(n_tok - 1):
                logits, caches = decode(
                    qparams, {"tokens": jnp.asarray([[tok]], jnp.int32)},
                    caches, jnp.asarray(pos))
                tok = int(np.argmax(np.asarray(
                    logits.astype(jnp.float32))[0, -1, :cfg.vocab]))
                out.append(tok)
                pos += 1
            np.testing.assert_array_equal(served[i], np.asarray(out))

    def test_quantized_batched_equals_one_by_one(self, llama, llama_plan):
        cfg, params = llama
        _, plan = llama_plan
        server = engine.InferenceServer(cfg, params, plan=plan,
                                        max_len=48, max_batch=2)
        prompts = _prompts(cfg, 2, 5, seed=6)
        sp = SamplingParams(temperature=0.7, top_k=12, max_tokens=5,
                            seed=2)
        both = server.serve([Request(uid=i, prompt=prompts[i], sampling=sp)
                             for i in range(2)])
        for i in range(2):
            solo = server.serve([Request(uid=i, prompt=prompts[i],
                                         sampling=sp)])
            np.testing.assert_array_equal(both[i], solo[i])

    def test_quantization_changes_decode(self, llama):
        """Sanity: a heavily-quantized plan actually drives the forward
        (2-bit weights on a random net must alter greedy decode)."""
        cfg, params = llama
        plan = engine.synthetic_plan(cfg, params, bits=2)
        s_float = engine.InferenceServer(cfg, params, max_len=48,
                                         max_batch=2)
        s_quant = engine.InferenceServer(cfg, params, plan=plan,
                                         max_len=48, max_batch=2)
        prompts = _prompts(cfg, 2, 6, seed=8)
        out_f = s_float.generate(prompts, n_tokens=8)
        out_q = s_quant.generate(prompts, n_tokens=8)
        assert not np.array_equal(out_f, out_q)

    def test_fully_pruned_group_serves(self, llama):
        cfg, params = llama
        plan = engine.synthetic_plan(cfg, params, bits=4)
        grp = sorted(plan.channel_bits)[0]
        plan.channel_bits[grp][:] = 0
        import repro.core.discretize as discretize
        plan.permutations[grp] = discretize.reorder_permutations(
            {"gamma": {grp: plan.channel_bits[grp]}})[grp]
        server = engine.InferenceServer(cfg, params, plan=plan,
                                        max_len=32, max_batch=2)
        out = server.generate(_prompts(cfg, 2, 4, seed=3), n_tokens=4)
        assert out.shape == (2, 4)
        assert out.max() < cfg.vocab

    def test_apply_plan_strict_on_missing_group(self, llama):
        cfg, params = llama
        plan = engine.synthetic_plan(cfg, params, bits=4)
        grp = sorted(plan.channel_bits)[0]
        del plan.channel_bits[grp]
        with pytest.raises(KeyError):
            engine.apply_plan(cfg, params, plan)
        qparams = engine.apply_plan(cfg, params, plan, strict=False)
        assert isinstance(qparams["blocks"], tuple)


# ---------------------------------------------------------------------------
# PeriodicEval assignment caching
# ---------------------------------------------------------------------------

class TestPeriodicEvalCache:
    def test_unchanged_gammas_discretize_once(self, monkeypatch):
        from repro import api
        from repro.api import phases as phases_mod
        from repro.core import discretize
        from repro.data import synthetic
        from repro.models import cnn

        g = cnn.dscnn(width=8)
        state = phases_mod.CompressionState(
            graph=g, spec=synthetic.GSC_LIKE, pw=(0, 2, 4, 8), px=(8,),
            batch=8, seed=0)
        state.folded = cnn.fold_batchnorm(
            g, cnn.init_params(g, jax.random.key(0)))
        js = api.JointSearch(steps=1)
        ts = js.init_train_state(state)

        calls = {"n": 0}
        real = discretize.assign

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(discretize, "assign", counting)
        pe = api.PeriodicEval(every=1, n_batches=1)
        r1 = pe.on_step(js, state, 0, {}, ts)
        r2 = pe.on_step(js, state, 1, {}, ts)
        assert calls["n"] == 1                  # second eval hit the cache
        assert len(state.metrics[js.name]) == 2
        # changed gammas invalidate the fingerprint
        ts["sp"]["mps"]["gamma"] = {
            k: v + 1.0 for k, v in ts["sp"]["mps"]["gamma"].items()}
        pe.on_step(js, state, 2, {}, ts)
        assert calls["n"] == 2


# ---------------------------------------------------------------------------
# the overlapped serve loop: step n+1 dispatched before step n is read back
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def granite():
    cfg = registry.get("granite-4.0-h-small-smoke")
    return cfg, lm.init_params(cfg, jax.random.key(3))


def _sync_reference(server, req):
    """``req``'s stream from a plain synchronous loop over the server's
    bound parameters: one request, dense caches, fused prefill, then one
    decode step at a time, each token read back before the next step is
    run.  Tokens are drawn as the server draws them: argmax when greedy,
    else the on-device sampler with the request's (seed, uid, index)."""
    cfg, params, sp = server.cfg, server.params, req.sampling
    prompt = np.asarray(req.prompt, np.int32)
    if not hasattr(server, "_ref_steps"):
        server._ref_steps = (
            jax.jit(steps.make_prefill_step(cfg)),
            jax.jit(lambda p, t, c, pos: lm.decode_step(cfg, p, t, c, pos)))
    prefill, decode = server._ref_steps
    caches = lm.init_caches(cfg, 1, server.max_len)
    logits, pc = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    caches = jax.tree.map(
        lambda big, small: jax.lax.dynamic_update_slice(
            big, small.astype(big.dtype), (0,) * big.ndim), caches, pc)

    def draw(row, tidx):
        row = row[:, :cfg.vocab]
        if sp.greedy:
            return int(np.argmax(np.asarray(row.astype(jnp.float32))[0]))
        tok = sample_tokens_device(
            row, jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_k], jnp.int32),
            jnp.asarray([sp.seed], jnp.int32),
            jnp.asarray([req.uid], jnp.int32),
            jnp.asarray([tidx], jnp.int32),
            need_top_k=0 < sp.top_k < cfg.vocab)
        return int(np.asarray(tok)[0])

    out = [draw(logits[:, -1].astype(jnp.float32), 0)]
    pos = prompt.size
    while len(out) < sp.max_tokens and pos < server.max_len:
        logits, caches = decode(
            params, {"tokens": jnp.asarray([[out[-1]]], jnp.int32)},
            caches, jnp.asarray([pos], jnp.int32))
        out.append(draw(logits[:, -1], len(out)))
        pos += 1
    return np.asarray(out, np.int32)


def _overlap_reqs(cfg, sampled, seed=0):
    """Four requests over two slots, arriving while others decode: two
    at step 0, two later; budgets differ so slots free at different
    steps, and one request finishes at admission."""
    rng = np.random.default_rng(seed)
    sps = [SamplingParams(max_tokens=6), SamplingParams(max_tokens=4),
           SamplingParams(max_tokens=1), SamplingParams(max_tokens=5)]
    if sampled:
        sps[0] = SamplingParams(temperature=0.8, top_k=12, max_tokens=6,
                                seed=11)
        sps[3] = SamplingParams(temperature=0.7, max_tokens=5, seed=4)
    lens, arrivals = (5, 9, 5, 9), (0, 0, 2, 3)
    return [Request(uid=i, sampling=sps[i], arrival=arrivals[i],
                    prompt=rng.integers(0, cfg.vocab, size=lens[i])
                    .astype(np.int32))
            for i in range(4)]


_SERVERS = {}


def _shared_server(arch, cfg, params, cache, plan):
    """One server per (arch, cache, plan) for the parity matrix, so its
    cases share compiled programs."""
    key = (arch, cache, plan)
    if key not in _SERVERS:
        _SERVERS[key] = _server(cfg, params, cache, plan)
    return _SERVERS[key]


def _server(cfg, params, cache, plan, **kw):
    plan = engine.synthetic_plan(cfg, params, seed=0) if plan else None
    paged = {"cache": "paged", "page_size": 4, "pages": 24} \
        if cache == "paged" else {}
    kw.setdefault("max_len", 32)
    kw.setdefault("max_batch", 2)
    return engine.InferenceServer(cfg, params, plan, **paged, **kw)


def _run(server, reqs, before_step=None):
    """Drive a session step by step; returns the finished streams and
    the StepResults."""
    server.begin(reqs)
    results = []
    while server.has_work:
        if before_step is not None:
            before_step(server, len(results))
        results.append(server.step())
        assert not results[-1].nan
    return server.end(), results


class TestOverlappedDecode:
    @pytest.mark.parametrize("cache", ["paged", "dense"])
    @pytest.mark.parametrize("plan", [False, True], ids=["float", "plan"])
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_streams_match_the_synchronous_reference(self, llama, cache,
                                                     plan, sampled):
        cfg, params = llama
        server = _shared_server("llama", cfg, params, cache, plan)
        reqs = _overlap_reqs(cfg, sampled)
        out, _ = _run(server, reqs)
        for r in reqs:
            np.testing.assert_array_equal(out[r.uid],
                                          _sync_reference(server, r))

    @pytest.mark.parametrize("cache,sampled", [("paged", True),
                                               ("dense", False)])
    def test_ssm_state_in_flight_matches_the_reference(self, granite, cache,
                                                       sampled):
        cfg, params = granite
        server = _shared_server("granite", cfg, params, cache, False)
        reqs = _overlap_reqs(cfg, sampled, seed=1)
        out, _ = _run(server, reqs)
        for r in reqs:
            np.testing.assert_array_equal(out[r.uid],
                                          _sync_reference(server, r))

    def test_admissions_while_a_step_is_in_flight(self, llama):
        cfg, params = llama
        server = _server(cfg, params, "paged", False)
        reqs = _overlap_reqs(cfg, sampled=True)
        in_flight = []
        out, results = _run(server, reqs, lambda srv, _: in_flight.append(
            bool(srv._flight)))
        # the late arrivals were admitted while a decode step was unread
        late = {r.uid for r in reqs if r.arrival > 0}
        assert any(late & set(res.admitted) and flying
                   for res, flying in zip(results, in_flight))
        # each admission's first token arrives in the call admitting it
        for res in results:
            for uid in res.admitted:
                assert uid in res.produced
        for r in reqs:
            np.testing.assert_array_equal(out[r.uid],
                                          _sync_reference(server, r))
        assert server.stats["memory"]["pages_in_use"] == 0

    def test_request_finishes_within_its_token_count_of_calls(self, llama):
        cfg, params = llama
        server = _server(cfg, params, "paged", False, max_batch=4)
        reqs = _overlap_reqs(cfg, sampled=False)
        _, results = _run(server, reqs)
        admitted_at = {uid: k for k, res in enumerate(results)
                       for uid in res.admitted}
        finished_at = {uid: k for k, res in enumerate(results)
                       for uid in res.finished}
        for r in reqs:
            assert finished_at[r.uid] - admitted_at[r.uid] + 1 \
                <= r.sampling.max_tokens

    def test_cancel_in_flight_slot_then_reuse_its_pages(self, llama):
        cfg, params = llama
        # six pages: the two requests hold five after two steps, so the
        # newcomer's three (two and the reservation) need the freed ones
        server = _server(cfg, params, "paged", False, max_batch=2)
        server.backend = cache_mod.make_backend(
            "paged", cfg, 2, 32, page_size=4, n_pages=6)
        reqs = _overlap_reqs(cfg, sampled=True)[:2]
        server.begin(reqs)
        server.step()                     # both admitted, step 1 in flight
        server.step()                     # step 2 in flight, 1 read back
        victim = next(st for st in server._sched.active
                      if st.request.uid == 0)
        assert victim.pending == 1        # its next token is unread
        pages = set(victim.handle.pages)
        held = server.backend.memory_report()["pages_in_use"]
        toks = server.cancel(0)
        assert toks.size == 2             # read-back tokens only
        assert server.backend.memory_report()["pages_in_use"] \
            == held - len(pages)
        newcomer = Request(uid=7, prompt=reqs[0].prompt[::-1].copy(),
                           sampling=SamplingParams(temperature=0.9,
                                                   top_k=5, max_tokens=5,
                                                   seed=2))
        server.submit(newcomer)
        res = server.step()               # the in-flight step runs first
        assert res.admitted == [7]
        st = next(s for s in server._sched.active if s.request.uid == 7)
        assert pages & set(st.handle.pages)   # the freed pages reused
        while server.has_work:
            server.step()
        out = server.end()
        assert set(out) == {1, 7}
        assert server.backend.memory_report()["pages_in_use"] == 0
        np.testing.assert_array_equal(out[7],
                                      _sync_reference(server, newcomer))
        np.testing.assert_array_equal(out[1],
                                      _sync_reference(server, reqs[1]))

    def test_preemption_with_a_step_in_flight(self, llama):
        cfg, params = llama
        sp = SamplingParams(temperature=0.6, top_k=10, max_tokens=8,
                            seed=3)
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, sampling=sp,
                        prompt=rng.integers(0, cfg.vocab, size=n)
                        .astype(np.int32))
                for i, n in enumerate((4, 9, 6, 13))]
        server = engine.InferenceServer(
            cfg, params, max_len=32, max_batch=3, cache="paged",
            page_size=4, pages=7, obs=obs.Observability())
        out, _ = _run(server, reqs)
        assert server.stats["preemptions"] > 0
        for r in reqs:
            np.testing.assert_array_equal(out[r.uid],
                                          _sync_reference(server, r))
        mode = server.obs.registry.counter("serve_decode_overlap_total",
                                           labels=("mode",))
        # a preemption reads the victim's in-flight token first, so the
        # step after it is dispatched with nothing unread
        assert mode.value(mode="synced") > 1
        assert server.stats["memory"]["pages_in_use"] == 0

    def test_nan_poisoned_plan_reaches_no_stream(self, llama):
        cfg, params = llama
        server = _server(cfg, params, "paged", True, max_batch=2)
        reqs = _overlap_reqs(cfg, sampled=False)[:2]
        reqs[1] = dataclasses.replace(
            reqs[1], sampling=SamplingParams(max_tokens=1))
        ref = {r.uid: _sync_reference(server, r) for r in reqs}
        # poisoned from the start: the first readback trips the guard,
        # and the request that would finish at admission does not
        undo = chaos_inject.poison_params(server)
        server.begin(reqs)
        res = server.step()
        assert res.nan and not res.finished and not res.produced
        assert server.step().nan          # the session stays poisoned
        assert [server.cancel(r.uid).size for r in reqs] == [0, 0]
        server.end()
        undo()
        # poisoned mid-session (a fresh server: the poisoned steps left
        # NaN in pages of the pool): the step read back after the
        # poisoning is clean, the next holds NaN, and the step behind it
        # is discarded
        reqs = _overlap_reqs(cfg, sampled=False)[:2]
        server = _server(cfg, params, "paged", True, max_batch=2)
        ref = {r.uid: _sync_reference(server, r) for r in reqs}
        server.begin(reqs)
        server.step()
        server.step()
        read = {st.request.uid: len(st.out) for st in server._sched.active}
        undo = chaos_inject.poison_params(server)
        try:
            assert not server.step().nan  # reads the last clean step
            res = server.step()
            assert res.nan and not res.finished and not res.produced
            for r in reqs:
                toks = server.cancel(r.uid, reason="quarantined")
                assert toks.size == read[r.uid] + 1
                np.testing.assert_array_equal(toks, ref[r.uid][:toks.size])
            server.end()
        finally:
            undo()

    @pytest.mark.parametrize("host", [False, True], ids=["greedy", "host"])
    def test_overlap_counter(self, llama, host):
        cfg, params = llama
        server = _server(cfg, params, "paged", False,
                         sample_on_device=not host, obs=obs.Observability())
        # three requests over two slots, budgets apart so a slot is
        # always decoding until the last token: every step but the first
        # is dispatched with its predecessor unread
        rng = np.random.default_rng(5)
        reqs = [Request(uid=i, sampling=SamplingParams(max_tokens=n),
                        prompt=rng.integers(0, cfg.vocab, size=5 + i)
                        .astype(np.int32))
                for i, n in enumerate((6, 9, 4))]
        _run(server, reqs)
        mode = server.obs.registry.counter("serve_decode_overlap_total",
                                           labels=("mode",))
        steps_ = server.stats["decode_steps"]
        if host:
            assert mode.value(mode="synced") == steps_
            assert mode.value(mode="overlapped") == 0
        else:
            assert mode.value(mode="synced") == 1
            assert mode.value(mode="overlapped") == steps_ - 1

    def test_compiled_decode_variants_over_a_walk(self, llama):
        """A solo walk across the live-width buckets compiles one greedy
        decode per width, as the step-by-step loop did, and one
        first-token sampler."""
        cfg, params = llama
        server = _server(cfg, params, "paged", False, max_len=64,
                         max_batch=2, obs=obs.Observability())
        prompt = np.arange(3, 12, dtype=np.int32)
        n = 40
        walk = Request(uid=0, prompt=prompt,
                       sampling=SamplingParams(max_tokens=n))
        out, _ = _run(server, [walk])
        assert out[0].size == n
        widths = {server._live_width([types.SimpleNamespace(pos=p)])
                  for p in range(prompt.size, prompt.size + n - 1)}
        assert len(widths) > 1
        assert server._decode_greedy._cache_size() == len(widths)
        assert server._decode_sample._cache_size() == 0
        assert server._sample._cache_size() == 1
        series = server.obs.registry.snapshot()[
            "serve_decode_steps_total"]["series"]
        assert {s["labels"]["width"] for s in series} == \
            {str(w) for w in widths}
        # a second session compiles nothing more
        _run(server, [walk])
        assert server._decode_greedy._cache_size() == len(widths)
        assert server._sample._cache_size() == 1
