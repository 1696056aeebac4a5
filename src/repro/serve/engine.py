"""Plan-driven serving stack.

Layers:
  * :class:`InferenceServer` -- the serving API.  Takes ``(cfg, params,
    plan)``; owns a continuous-batching scheduler (new requests are
    admitted into decode slots as others finish), a pluggable
    :class:`~repro.serve.cache.CacheBackend` (``cache="dense"`` keeps the
    historical dense slot buffers, ``cache="paged"`` virtualizes them
    behind a page pool + block tables so cache memory scales with live
    tokens), fused prefill (one full-sequence forward via
    ``launch.steps``, page-bucketed under paging), per-request
    :class:`SamplingParams` drawn **on device** inside the jitted decode
    step (Gumbel top-k, per-request fold_in'd keys; host fallback via
    ``sample_on_device=False``), and -- when a
    :class:`~repro.api.plan.CompressionPlan` is given -- end-to-end
    quantized decode: every planned projection is bound to a
    :class:`~repro.nn.quantized.PackedLinear` and served through
    ``mixed_precision_matmul`` inside the jitted forward.
  * :func:`apply_plan` -- binds a plan into an LM parameter tree.
  * export/apply of *discretized* layers (paper Fig. 3): per-layer packing
    shared with the in-forward path via ``repro.nn.quantized``.
  * :class:`ServeEngine` -- thin backward-compatible shim over
    :class:`InferenceServer` (greedy, all-at-once batch).

The cache-backend contract is *token-for-token invariance*: dense and
paged backends, solo and batched and streaming, with or without a plan,
all emit identical token streams -- the serving tests assert exactly
that.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import ops as paged_ops
from repro.launch import steps
from repro.models import lm
from repro.nn import quantized as nnq
from repro.obs import run_summary, span
from repro.serve import cache as cache_mod
from repro.serve.sampling import (SamplingParams, batch_need_top_k,
                                  make_rng, sample_token,
                                  sample_tokens_device)
from repro.serve.scheduler import Request, Scheduler, SlotState


# ---------------------------------------------------------------------------
# plan binding: CompressionPlan -> servable parameter tree
# ---------------------------------------------------------------------------

def apply_plan(cfg, params, plan, strict: bool = True):
    """Bind a :class:`CompressionPlan` into an LM parameter tree.

    Every plan group (see ``lm.serve_weight_groups`` for the naming) has
    its float projection replaced by a bit-packed
    :class:`~repro.nn.quantized.PackedLinear` built from the plan's
    recorded channel bits AND its stored Fig. 3 permutation, so a
    saved+loaded plan serves byte-identically to the in-memory one.

    Because packed buffer shapes differ per layer, the returned tree keeps
    ``blocks`` as a *tuple of per-super-block trees* (the forward unrolls
    instead of scanning).  Gammas are dropped; non-quantizable weights
    (MoE expert banks, routers, norms) are sliced per super-block and stay
    float.  ``strict=False`` leaves groups missing from the plan in float
    instead of raising.
    """
    tmpl = lm.abstract_params(cfg, mps_on=True)["blocks"]
    nsb = lm.n_superblocks(cfg)

    def build(tnode, pnode, path, j):
        if isinstance(pnode, dict):
            if (isinstance(tnode, dict) and "w" in tnode
                    and "gamma" in tnode and tnode["w"].ndim == 3):
                group = f"{path}.sb{j}"
                if group in plan.channel_bits:
                    # index on device first: the whole (nsb, K, N) stack
                    # would cross to the host once per super-block
                    w = np.asarray(pnode["w"][j], np.float32)   # (K, N)
                    return {"w": nnq.PackedLinear.from_dense(
                        w, plan.channel_bits[group],
                        perm=plan.permutations[group])}
                if strict:
                    raise KeyError(
                        f"plan has no group {group!r} (plan groups: "
                        f"{len(plan.channel_bits)}; pass strict=False to "
                        f"serve unplanned projections in float)")
                return {"w": jnp.asarray(pnode["w"][j])}
            return {k: build(tnode.get(k) if isinstance(tnode, dict)
                             else None, v, f"{path}.{k}", j)
                    for k, v in pnode.items() if k != "gamma"}
        return pnode[j]          # stacked (nsb, ...) leaf -> this block's

    blocks_q = tuple(
        {lname: build(tmpl[lname], params["blocks"][lname],
                      f"blocks.{lname}", j)
         for lname in params["blocks"]}
        for j in range(nsb))
    out = dict(params)
    out["blocks"] = blocks_q
    return out


def synthetic_plan(cfg, params, bits: int | None = None, seed: int = 0,
                   pw=(0, 2, 4, 8)):
    """A deterministic demo/benchmark plan over the LM's plan groups:
    uniform ``bits`` everywhere, or (``bits=None``) a seeded random mix
    drawn from ``pw``.  Not searched -- useful for smoke tests, the
    ``--plan demo`` launcher mode and throughput benchmarks."""
    from repro.api.plan import CompressionPlan

    rng = np.random.default_rng(seed)
    # favour the higher precisions (linearly), light pruning mass on 0-bit
    weights_p = np.arange(1, len(pw) + 1, dtype=np.float64)
    p = weights_p / weights_p.sum()
    gamma = {}
    for grp, w in lm.serve_weight_groups(cfg, params).items():
        c = w.shape[0]
        if bits is None:
            gamma[grp] = rng.choice(pw, size=c, p=p).astype(np.int64)
        else:
            gamma[grp] = np.full((c,), int(bits), np.int64)
    assignment = {"gamma": gamma, "delta": {}, "alpha": {}}
    return CompressionPlan.from_assignment(
        assignment, pw, (8,), meta={"track": "lm", "arch": cfg.name,
                                    "synthetic": True,
                                    "bits": bits, "seed": seed})


# ---------------------------------------------------------------------------
# the serving API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepResult:
    """What one :meth:`InferenceServer.step` did.

    ``produced`` maps uid -> tokens generated so far, for every request
    that gained a token this step (admission token or decode token);
    ``idle`` means no decode ran (the engine jumped the clock to the
    next arrival, or had nothing at all to do).  ``nan`` means NaN
    logits were detected at the sampling host boundary: the step's
    tokens were DISCARDED (no stream advanced, nothing finished) and
    the caller should quarantine the server -- the fleet's failover
    path recovers the in-flight requests onto healthy replicas."""

    admitted: list
    produced: dict
    finished: list
    idle: bool = False
    nan: bool = False


class InferenceServer:
    """Plan-driven LM serving with continuous batching.

    ``plan=None`` serves float weights; a :class:`CompressionPlan` switches
    the whole decode path to quantized execution (see :func:`apply_plan`).
    ``cache="paged"`` swaps the dense per-slot KV buffers for a
    :class:`~repro.serve.cache.PagedCache` (page pool + block tables,
    memory-aware admission, preemption-to-queue on pool exhaustion) --
    token streams are identical on both backends.  Decoder-only
    token-frontend architectures only (enc-dec and vision/audio frontends
    need prompt-side encoders the request schema doesn't carry yet).
    """

    def __init__(self, cfg, params, plan=None, *, max_len: int = 512,
                 max_batch: int = 8, strict_plan: bool = True,
                 cache: str = "dense", page_size: int = 16,
                 pages: int | None = None, reserve_pages: int = 1,
                 sample_on_device: bool = True, obs=None):
        if cfg.is_encdec or cfg.frontend != "none":
            raise NotImplementedError(
                f"InferenceServer serves decoder-only token-frontend "
                f"architectures; got {cfg.name} (family={cfg.family}, "
                f"frontend={cfg.frontend})")
        self.cfg = cfg
        self.plan = plan
        self.max_len = int(max_len)
        self.max_batch = int(max_batch)
        self.params = params if plan is None else apply_plan(
            cfg, params, plan, strict=strict_plan)
        self.sample_on_device = bool(sample_on_device)
        self.stats: dict = {}

        kwargs = {} if cache == "dense" else {
            "page_size": page_size, "n_pages": pages,
            "reserve_pages": reserve_pages}
        self.backend = cache_mod.make_backend(cache, cfg, self.max_batch,
                                              self.max_len, **kwargs)
        # paged prefill writes the prompt's KV straight into the page
        # pool (no dense round-trip; see make_paged_prefill_step).
        # Attention-only stacks pad the prompt to a q-chunk boundary --
        # the coarser of one sublane tile (8) and the page bucket,
        # capped at PREFILL_Q -- one compile per (padded length, table
        # width), never prefilling past the page bucket the retired
        # dense path used; an SSM mixer's recurrent state would absorb
        # the padding, so SSM/hybrid archs prefill at exact length
        # (compiled per prompt length), still straight into the pool.
        # Pure-SSM stacks have no KV pages at all and take the dense
        # prefill step (per-slot state insert only).
        self._has_ssm = any(spec.mixer == "mamba"
                            for spec in lm.block_pattern(cfg))
        self._paged_kv = (self.backend.name == "paged"
                          and getattr(self.backend, "_has_kv", False))
        # labels of the last admission's prefill on
        # serve_prefill_tokens_total (set by _run_prefill)
        self._prefill_path = "dense"
        self._prefill_width = "dense"

        self._prefill = jax.jit(steps.make_prefill_step(cfg))
        # donate the cache tree: decode updates it in place instead of
        # copying the full pool buffers per token (no-op on CPU, where
        # XLA ignores donation).  The paged block tables ride OUTSIDE
        # the donated tree so the backend's device copy survives across
        # steps (None for the dense backend); `width` is the STATIC
        # live-page prefix this step attends over -- sliced inside the
        # jit, so it costs one compile per distinct width (bounded by
        # table_width) instead of any per-step work, and attention
        # scans only pages some slot actually wrote instead of max_len.
        def _live_tables(tables, width):
            if tables is None or width is None \
                    or width >= tables.shape[1]:
                return tables
            return jax.lax.slice_in_dim(tables, 0, width, axis=1)

        # paged prefill: the slot's block-table row is sliced ON DEVICE
        # from the backend's resident tables (slot is traced -- no
        # per-slot compile, no per-admission host upload beyond alloc's
        # incremental row patch) and narrowed to the static live width;
        # the kv pool tree is donated so the prompt scatter is in place
        _paged_prefill = steps.make_paged_prefill_step(cfg)

        def prefill_paged(p, tok, kv, tbl, slot, lens, width):
            row = jax.lax.dynamic_slice_in_dim(tbl, slot, 1, axis=0)
            return _paged_prefill(p, tok, kv, _live_tables(row, width),
                                  lens)

        self._prefill_paged = jax.jit(prefill_paged, donate_argnums=(2,),
                                      static_argnums=(6,))

        self._decode = jax.jit(
            lambda p, t, c, tbl, pos, width: lm.decode_step(
                cfg, p, t, c, pos, tables=_live_tables(tbl, width)),
            donate_argnums=(2,), static_argnums=(5,))

        vocab = cfg.vocab

        def decode_sample(params, tokens, caches, tables, pos, temps,
                          topks, seeds, uids, tidx, need_top_k, width):
            """One decode step + on-device batched sampling: only the
            (B,) sampled ids (plus the scalar NaN-guard flag) cross back
            to the host."""
            logits, caches = lm.decode_step(
                cfg, params, tokens, caches, pos,
                tables=_live_tables(tables, width))
            with jax.named_scope("sample"):
                row = logits[:, -1, :vocab]
                next_tok = sample_tokens_device(
                    row, temps, topks, seeds, uids, tidx,
                    need_top_k=need_top_k)
                return next_tok, caches, jnp.isnan(row).any()

        self._decode_sample = jax.jit(decode_sample, donate_argnums=(2,),
                                      static_argnums=(10, 11))

        def decode_greedy(params, tokens, caches, tables, pos, width):
            """All-greedy fast path: plain argmax, no sort/Gumbel work."""
            logits, caches = lm.decode_step(
                cfg, params, tokens, caches, pos,
                tables=_live_tables(tables, width))
            with jax.named_scope("sample"):
                row = logits[:, -1, :vocab].astype(jnp.float32)
                next_tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
                return next_tok, caches, jnp.isnan(row).any()

        self._decode_greedy = jax.jit(decode_greedy, donate_argnums=(2,),
                                      static_argnums=(5,))
        # the NaN-guard flag rides back with the sampled id: a scalar
        # crossing an already-paid host boundary, so corrupted (e.g.
        # NaN-poisoned-plan) logits are caught before a garbage token
        # can enter a client stream
        def sample(lg, temps, topks, seeds, uids, tidx, need_top_k):
            with jax.named_scope("sample"):
                return (sample_tokens_device(lg[:, :vocab], temps, topks,
                                             seeds, uids, tidx,
                                             need_top_k=need_top_k),
                        jnp.isnan(lg[:, :vocab]).any())

        self._sample = jax.jit(sample, static_argnums=(6,))
        # session state (see the "serving" section): None between runs
        self._sched = None
        self._now = 0
        self._n_steps = 0
        self._n_admitted = 0
        self._cancelled: dict = {}
        self._nan_detected = False
        self.obs = None
        self._reg = None
        self.attach_obs(obs)

    # ------------------------------------------------------- observability
    def attach_obs(self, obs):
        """Attach (or with ``obs=None`` detach) a
        :class:`repro.obs.Observability` bundle.  Instrumentation is
        host-side only -- the jitted closures are untouched, so this can
        be called on an already-warmed server without triggering
        recompiles (``benchmarks/serve_bench.py`` relies on that to
        measure obs overhead on identical compiled code)."""
        self.obs = obs
        reg = None
        if obs is not None and obs.registry.enabled:
            reg = obs.registry
        self._reg = reg
        self.backend.bind_metrics(reg)

    def metrics_snapshot(self) -> dict:
        """Current metrics + (when tracing) the last serve run's summary;
        ``{}`` when no Observability bundle is attached."""
        if self.obs is None:
            return {}
        self.backend.publish_metrics()
        out = {"metrics": (self.obs.registry.snapshot()
                           if self.obs.registry.enabled else {})}
        if self.obs.tracer is not None:
            out["summary"] = run_summary(self.obs.tracer,
                                         self.obs.registry)
        out["load"] = self.load_report()
        return out

    # ------------------------------------------------------- sampling glue
    def _sample_first(self, logits_last, st_req, uid, tidx, rng):
        """Sample from prefill logits (token index ``tidx`` of the
        request's stream): device path or host fallback."""
        if self.sample_on_device:
            sp = st_req.sampling
            tok, bad = self._sample(
                logits_last.astype(jnp.float32),
                jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.seed], jnp.int32),
                jnp.asarray([uid], jnp.int32),
                jnp.asarray([tidx], jnp.int32),
                0 < sp.top_k < self.cfg.vocab)
            with span("serve.device_wait"):
                bad = bool(np.asarray(bad))
            if bad:
                self._flag_nan()
            with span("serve.device_wait"):
                return int(np.asarray(tok)[0])
        with span("serve.device_wait"):
            row = np.asarray(logits_last.astype(jnp.float32))[0]
        vrow = row[: self.cfg.vocab]
        if np.isnan(vrow).any():
            self._flag_nan()
            return 0        # untrusted step; never reaches `finished`
        return sample_token(vrow, st_req.sampling, rng)

    def _flag_nan(self):
        """Record a NaN detection at the sampling host boundary.  The
        flag makes the current step's tokens untrusted: ``step()``
        discards them and reports ``StepResult.nan``, and ``serve()``
        raises (a solo server has no failover path)."""
        self._nan_detected = True
        if self._reg is not None:
            self._reg.counter(
                "fault_nan_detected_total",
                "NaN logits detected at the sampling host boundary"
            ).inc()

    # ------------------------------------------------------------ serving
    #
    # The serving loop is a *session*: ``begin()`` opens one (resetting
    # the cache backend and per-run trace), ``submit()`` enqueues,
    # ``step()`` advances one admission+decode round, ``cancel()``
    # removes a request mid-flight, ``end()`` closes the session and
    # returns the finished streams.  ``serve()`` is the batch
    # convenience wrapping the four; the fleet drives sessions directly
    # so it can interleave arrivals, deadline scans and cancellations
    # with decode steps.

    def begin(self, requests=(), *, fresh_trace: bool = True):
        """Open a serving session (per-run trace reset, fresh scheduler,
        cache backend reset) and submit ``requests``.
        ``fresh_trace=False`` keeps the tracer's events and time origin
        -- the fleet's crash-restore path reopens a struck replica's
        session without erasing its crashed/recovered history."""
        tracer = self.obs.tracer if self.obs is not None else None
        if tracer is not None and fresh_trace:
            tracer.start()          # per-run trace; metrics cumulative
        self._sched = Scheduler(self.max_batch, self.max_len,
                                tracer=tracer)
        self.backend.reset()
        self._now = 0
        self._n_steps = 0
        self._n_admitted = 0
        self._cancelled: dict = {}   # uid -> (reason, tokens np.ndarray)
        self._nan_detected = False
        for r in requests:
            self.submit(r)
        return self

    def submit(self, request, *, front: bool = False, trace_extra=None):
        """Enqueue a request into the open session (feasibility-checked
        against the cache backend's admission contract).  ``front=True``
        enqueues at the front of the queue -- the fleet's failover path
        preserves FCFS seniority of recovered requests this way --
        and ``trace_extra`` keys ride on the ``enqueued`` trace event."""
        if self._sched is None:
            raise RuntimeError("no open session; call begin() first")
        self.backend.check_feasible(np.asarray(request.prompt).size,
                                    request.sampling.max_tokens)
        self._sched.submit(request, front=front, trace_extra=trace_extra)
        if self._reg is not None:
            self._reg.counter("serve_requests_total",
                              "Requests submitted to serve()").inc()

    @property
    def has_work(self) -> bool:
        return self._sched is not None and self._sched.has_work

    def _admit(self) -> list:
        """Admit every arrived request the backend has memory for;
        returns the admitted uids (in admission order)."""
        sched, backend = self._sched, self.backend
        reg, tracer = self._reg, (self.obs.tracer
                                  if self.obs is not None else None)
        admitted = []
        while True:
            adm = sched.pop_admissible(
                self._now, can_admit=lambda e: backend.can_admit(
                    e.tokens().size))
            if adm is None:
                break
            entry, slot = adm
            req = entry.request
            resumed = entry.resume is not None
            tokens_np = entry.tokens()
            handle = backend.alloc(req.uid, slot, tokens_np.size)
            if tracer is not None:
                tracer.event(req.uid, "admitted", n=tokens_np.size,
                             pages_held=len(handle.pages), slot=slot,
                             resumed=resumed)
            if reg is not None:
                reg.counter(
                    "serve_admissions_total",
                    "Requests admitted into a decode slot",
                    labels=("resumed",)).inc(
                    resumed="true" if resumed else "false")
            with span("serve.prefill", uid=req.uid):
                logits = self._run_prefill(backend, handle, tokens_np)
            if tracer is not None:
                tracer.event(req.uid, "prefilled", n=tokens_np.size,
                             pages_held=len(handle.pages), slot=slot)
            if reg is not None:
                # one series per (path, width) == one compiled prefill
                # variant on the paged path (width is a static argument
                # of the jit; "dense"/"dense" for the dense backend and
                # pure-SSM stacks)
                reg.counter("serve_prefill_tokens_total",
                            "Tokens run through prefill (resumes "
                            "re-prefill prompt + generated) by prefill "
                            "path and static live-table width",
                            labels=("path", "width")).inc(
                    int(tokens_np.size), path=self._prefill_path,
                    width=self._prefill_width)
            self._n_admitted += 1
            if entry.resume is None:
                rng = make_rng(req.sampling, req.uid)
                with span("serve.sample_first", uid=req.uid):
                    tok = self._sample_first(logits, req, req.uid, 0, rng)
                st = SlotState(request=req, slot=slot,
                               pos=int(tokens_np.size),
                               remaining=req.sampling.max_tokens - 1,
                               last_token=tok, out=[tok], rng=rng,
                               order=self._n_admitted, handle=handle)
            else:       # preempted request: continue its exact stream
                st = entry.resume
                with span("serve.sample_first", uid=req.uid):
                    tok = self._sample_first(logits, req, req.uid,
                                             len(st.out), st.rng)
                st.slot = slot
                st.pos = int(tokens_np.size)
                st.out.append(tok)
                st.last_token = tok
                st.remaining -= 1
                st.order = self._n_admitted
                st.handle = handle
            if tracer is not None:
                # first residency yields the request's first token;
                # a resume's admission token is a decode step of its
                # ongoing stream
                tracer.event(req.uid,
                             "decode" if resumed else "first_token",
                             n=len(st.out),
                             pages_held=len(handle.pages), slot=slot)
            sched.activate(slot, st)
            admitted.append(req.uid)
            # a NaN-flagged admission token is untrusted: leave the
            # request resident so the quarantine/recovery path can
            # strike it instead of letting garbage into `finished`
            if (st.remaining <= 0 or st.pos >= self.max_len) \
                    and not self._nan_detected:
                st.truncated = st.remaining > 0
                backend.free(handle)
                sched.complete(slot)
        return admitted

    def step(self) -> StepResult:
        """One admission + batched-decode round of the open session."""
        if self._sched is None:
            raise RuntimeError("no open session; call begin() first")
        with span("serve.step"):
            return self._step()

    def _step(self) -> StepResult:
        sched, backend = self._sched, self.backend
        tracer = self.obs.tracer if self.obs is not None else None
        fin0 = len(sched.finished)
        with span("serve.admit"):
            admitted = self._admit()
        # every admission yields one token (sampled from the prefill
        # logits), so admitted uids are producers this step
        produced = {}
        for uid in admitted:
            st = sched.finished.get(uid) or next(
                (s for s in sched.active if s.request.uid == uid), None)
            if st is not None:
                produced[uid] = len(st.out)
        if self._nan_detected:
            # admission sampling tripped the NaN guard: nothing
            # completed (see _admit); surface and skip the decode
            return StepResult(admitted=admitted, produced=produced,
                              finished=list(sched.finished)[fin0:],
                              nan=True)

        active = sched.active
        idle = False
        if not active:
            nxt = sched.next_arrival
            if nxt is not None:
                self._now = max(self._now + 1, nxt)   # jump to arrival
            idle = True
        else:
            # one batched decode step over the active slots
            next_toks = self._decode_active(active)
            self._n_steps += 1
            if self._nan_detected:
                # discard the whole step's tokens: no stream advances,
                # nothing completes, the caller quarantines the server
                return StepResult(admitted=admitted, produced=produced,
                                  finished=list(sched.finished)[fin0:],
                                  nan=True)
            with span("serve.bookkeep"):
                self._bookkeep(active, next_toks, produced, tracer)
            self._now += 1
        finished = list(sched.finished)[fin0:]
        return StepResult(admitted=admitted, produced=produced,
                          finished=finished, idle=idle)

    def _bookkeep(self, active, next_toks, produced, tracer):
        """Record each slot's decoded token, free the finished, and back
        the survivors' next cache write."""
        sched, backend = self._sched, self.backend
        survivors = []
        for st in active:
            st.pos += 1
            tok = next_toks[st.slot]
            st.out.append(tok)
            st.last_token = tok
            st.remaining -= 1
            produced[st.request.uid] = len(st.out)
            if tracer is not None:
                tracer.event(st.request.uid, "decode", n=len(st.out),
                             pages_held=len(st.handle.pages),
                             slot=st.slot)
            if st.remaining <= 0:
                backend.free(st.handle)
                sched.complete(st.slot)
            elif st.pos >= self.max_len:
                st.truncated = True
                backend.free(st.handle)
                sched.complete(st.slot)
            else:
                survivors.append(st)
        # page-backing AFTER every slot recorded its token: a
        # preemption victim then always requeues with its full
        # sampled stream (resume re-derives nothing)
        for st in survivors:
            if sched.slots[st.slot] is st:   # not already preempted
                self._append_or_preempt(sched, backend, st)

    def cancel(self, uid: int, reason: str = "cancelled"):
        """Cancel a queued or in-flight request, freeing its cache pages
        immediately (``memory_report()`` returns to its pre-admission
        level).  ``reason`` is ``"cancelled"``, ``"timeout"``, or one of
        the fault terminals ``"crashed"``/``"quarantined"`` used by the
        fleet's failover path, and becomes the lifecycle terminal
        event.  Returns the tokens the request had generated so far
        (possibly empty), or None if the uid is not live in the
        session."""
        if reason not in ("cancelled", "timeout", "crashed",
                          "quarantined"):
            raise ValueError(f"cancel reason must be 'cancelled', "
                             f"'timeout', 'crashed' or 'quarantined', "
                             f"got {reason!r}")
        if self._sched is None:
            raise RuntimeError("no open session; call begin() first")
        sched = self._sched
        for st in sched.active:
            if st.request.uid == uid:
                self.backend.free(st.handle)   # before the event: the
                break                          # trace shows pages_held=0
        res = sched.cancel(uid, kind=reason)
        if res is None:
            return None
        where, obj = res
        if where == "pending":
            out = obj.resume.out if obj.resume is not None else []
        else:
            out = obj.out
        toks = np.asarray(out, np.int32)
        self._cancelled[uid] = (reason, toks)
        if self._reg is not None:
            self._reg.counter(
                "serve_cancelled_total",
                "Requests removed by cancel(), by reason",
                labels=("reason",)).inc(reason=reason)
        return toks

    def end(self) -> dict:
        """Close the session: final stats + metrics publish; returns
        ``{uid: np.ndarray(tokens)}`` for every finished request."""
        sched = self._sched
        if sched is None:
            raise RuntimeError("no open session; call begin() first")
        reasons = [r for r, _ in self._cancelled.values()]
        self.stats = {"decode_steps": self._n_steps,
                      "admitted": self._n_admitted,
                      "preemptions": sched.preemptions,
                      "generated": sum(len(s.out)
                                       for s in sched.finished.values()),
                      "cancelled": reasons.count("cancelled"),
                      "timeouts": reasons.count("timeout"),
                      "memory": self.backend.memory_report()}
        self.backend.publish_metrics()
        out = {uid: np.asarray(s.out, np.int32)
               for uid, s in sched.finished.items()}
        self._sched = None
        return out

    def live_uids(self) -> list:
        """Every live (queued or resident) uid in FCFS seniority order;
        the fleet's failover path walks this to recover a crashed or
        quarantined replica's in-flight requests."""
        if self._sched is None:
            return []
        return self._sched.live_uids()

    def result(self, uid: int):
        """Finished tokens for ``uid`` in the open session, else None."""
        if self._sched is not None and uid in self._sched.finished:
            return np.asarray(self._sched.finished[uid].out, np.int32)
        return None

    @property
    def preemption_counts(self) -> dict:
        """uid -> times preempted, for the open session."""
        if self._sched is None:
            return {}
        return dict(self._sched.preempt_counts)

    def load_report(self) -> dict:
        """Queue/slot/page occupancy: what routers key off.  Cheap --
        pure host-side bookkeeping, no device sync."""
        if self._sched is not None:
            load = self._sched.load()
        else:
            load = {"queued": 0, "active": 0,
                    "queued_tokens": 0, "active_tokens": 0}
        load["pages_in_use"] = int(
            self.backend.memory_report().get("pages_in_use", 0))
        # decode-step progress counter: the fleet's health watchdog
        # compares successive readings to detect a stalled replica
        load["steps"] = self._n_steps
        return load

    def serve(self, requests) -> dict:
        """Run every request to completion with continuous batching.

        Requests whose ``arrival > 0`` join the queue at that decode step
        (streaming-arrivals mode); more requests than ``max_batch`` (or
        than the page pool can hold at once -- the backend's admission
        contract) simply queue for capacity.  Returns
        ``{uid: np.ndarray(tokens)}``.
        """
        self.begin(requests)
        while self.has_work:
            if self.step().nan:
                # a solo server has no failover path: refuse to loop on
                # poisoned logits (the fleet quarantines instead)
                self.end()
                raise RuntimeError(
                    "NaN logits detected at the sampling host boundary; "
                    "serving aborted (corrupted parameters or plan?)")
        return self.end()

    def _run_prefill(self, backend, handle, tokens_np):
        """Fused full-sequence prefill; insert KV/SSM into the backend.
        Paged KV stacks prefill straight into the page pool: the pool
        tree is donated into the jit, so the prompt's K/V lands in the
        request's pages in place -- no dense-shaped KV round-trip, no
        per-admission scatter dispatch.  Returns the (1, V_pad) logits
        of the last real prompt token."""
        s = int(tokens_np.size)
        # numpy operands go straight into the jit call (one C++-side
        # device put each) -- per-admission python-dispatched puts are
        # pure TTFT overhead
        if self._paged_kv:
            q = min(paged_ops.PREFILL_Q, max(8, backend.page_size))
            spad = s if self._has_ssm else -(-s // q) * q
            padded = np.zeros((1, spad), np.int32)
            padded[0, :s] = tokens_np
            width = min(-(-spad // backend.page_size),
                        backend.table_width)
            logits, pcaches = self._prefill_paged(
                self.params, {"tokens": padded},
                backend.kv_caches(), backend.device_tables(),
                np.int32(handle.slot), np.asarray([s], np.int32), width)
            self._prefill_path = "paged"
            self._prefill_width = str(width)
        else:
            logits, pcaches = self._prefill(
                self.params, {"tokens": tokens_np[None]})
            self._prefill_path = "dense"
            self._prefill_width = "dense"
        backend.insert(handle, pcaches)
        return logits[:, -1, :]

    def _live_width(self, active):
        """Live block-table width for this step: enough pages to cover
        the highest decode position in the batch.  Pages past it were
        never written by ANY slot -- the paged attention then scans the
        live prefix instead of the full ``max_len`` width (dense
        attention always pays the full width).  Each distinct width is
        one extra compile of the decode step, so widths are bucketed to
        at most 8 values per table (exact below 8 pages): a realistic
        max_len/page_size of 128 pages still compiles <= 8 variants,
        each at most table_width/8 pages wider than needed.
        """
        if self.backend.name != "paged":
            return None
        tw = self.backend.table_width
        need = max(st.pos for st in active) // self.backend.page_size + 1
        step = max(1, tw // 8)
        return min(tw, -(-need // step) * step)

    def _decode_active(self, active) -> dict:
        """One batched decode step; returns {slot: sampled token id}."""
        # which decode callable runs (the metrics label): when every
        # active row is greedy, the argmax decode, none of the sort/Gumbel
        # machinery (bit-identical to the full sampler)
        if not self.sample_on_device:
            path = "host"
        elif all(st.request.sampling.greedy for st in active):
            path = "greedy"
        else:
            path = "sample"
        with span("serve.decode.inputs"):
            tokens = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            for st in active:
                tokens[st.slot, 0] = st.last_token
                pos[st.slot] = st.pos
            caches = self.backend.gather()
            tables = self.backend.device_tables()
            width = self._live_width(active)
            if path == "sample":
                sampling = self._sampling_inputs(active)
        try:
            if path == "host":
                with span("serve.decode.launch"):
                    logits, caches = self._decode(
                        self.params, {"tokens": jnp.asarray(tokens)},
                        caches, tables, jnp.asarray(pos), width)
                    self.backend.commit(caches)
                with span("serve.device_wait"):
                    rows = np.asarray(logits.astype(jnp.float32))[
                        :, -1, : self.cfg.vocab]
                if any(np.isnan(rows[st.slot]).any() for st in active):
                    self._flag_nan()
                    # don't sample from poisoned rows (the host
                    # sampler's softmax would propagate the NaN); step()
                    # discards the step's tokens anyway
                    return {st.slot: 0 for st in active}
                return {st.slot: sample_token(rows[st.slot],
                                              st.request.sampling, st.rng)
                        for st in active}
            with span("serve.decode.launch"):
                if path == "greedy":
                    next_tok, caches, bad = self._decode_greedy(
                        self.params, {"tokens": jnp.asarray(tokens)},
                        caches, tables, jnp.asarray(pos), width)
                else:
                    next_tok, caches, bad = self._decode_sample(
                        self.params, {"tokens": jnp.asarray(tokens)},
                        caches, tables, jnp.asarray(pos), *sampling,
                        width)
                self.backend.commit(caches)
            with span("serve.device_wait"):
                bad = bool(np.asarray(bad))
            if bad:
                self._flag_nan()
            with span("serve.device_wait"):
                ids = np.asarray(next_tok)
            return {st.slot: int(ids[st.slot]) for st in active}
        finally:
            if self._reg is not None:
                # one series per (path, width) == one compiled decode
                # variant (width is a static argument of the jit)
                self._reg.counter(
                    "serve_decode_steps_total",
                    "Batched decode steps by decode path and static "
                    "live-table width",
                    labels=("path", "width")).inc(
                    path=path,
                    width="dense" if width is None else str(width))

    def _sampling_inputs(self, active) -> tuple:
        """The on-device sampler's per-slot operands, and its trace-time
        flag: rows that truncate need the full-vocab sort; a
        pure-temperature batch skips it entirely."""
        temps = np.zeros(self.max_batch, np.float32)
        topks = np.zeros(self.max_batch, np.int32)
        seeds = np.zeros(self.max_batch, np.int32)
        uids = np.zeros(self.max_batch, np.int32)
        tidx = np.zeros(self.max_batch, np.int32)
        for st in active:
            sp = st.request.sampling
            temps[st.slot] = sp.temperature
            topks[st.slot] = sp.top_k
            seeds[st.slot] = sp.seed
            uids[st.slot] = st.request.uid
            tidx[st.slot] = len(st.out)
        need_top_k = batch_need_top_k(
            [st.request.sampling for st in active], self.cfg.vocab,
            self._reg)
        return (jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(seeds),
                jnp.asarray(uids), jnp.asarray(tidx), need_top_k)

    def _append_or_preempt(self, sched, backend, st):
        """Back the request's next cache write with storage; on pool
        exhaustion preempt the youngest-admitted active request (vLLM
        recompute-style) until the append succeeds or ``st`` itself was
        evicted."""
        while True:
            try:
                backend.append(st.handle)
                return
            except cache_mod.PoolExhausted:
                victim = max(sched.active, key=lambda s: s.order)
                backend.free(victim.handle)
                sched.preempt(victim.slot)
                if self._reg is not None:
                    self._reg.counter(
                        "serve_preemptions_total",
                        "Requests preempted back to the queue on pool "
                        "exhaustion").inc()
                if victim is st:
                    return

    def generate(self, prompts: np.ndarray, sampling=None,
                 n_tokens: int | None = None) -> np.ndarray:
        """Batch convenience: (B, S0) prompts -> (B, max_tokens) tokens.

        ``sampling`` is one :class:`SamplingParams` shared by every prompt
        or a per-prompt list; default greedy ``n_tokens`` continuation.
        """
        prompts = np.asarray(prompts, np.int32)
        b = prompts.shape[0]
        if sampling is None:
            sampling = SamplingParams(max_tokens=n_tokens or 16)
        per = list(sampling) if isinstance(sampling, (list, tuple)) \
            else [sampling] * b
        if len(per) != b:
            raise ValueError(f"got {len(per)} SamplingParams for "
                             f"{b} prompts")
        if len({sp.max_tokens for sp in per}) > 1:
            raise ValueError(
                "generate() stacks completions into one (B, max_tokens) "
                "array, so per-prompt max_tokens must match; use serve() "
                "for heterogeneous token budgets")
        reqs = [Request(uid=i, prompt=prompts[i], sampling=per[i])
                for i in range(b)]
        res = self.serve(reqs)
        return np.stack([res[i] for i in range(b)])


# ---------------------------------------------------------------------------
# legacy shim
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeEngine:
    """Deprecated thin shim over :class:`InferenceServer` (greedy,
    all-at-once batch).  New code should use InferenceServer directly."""

    cfg: object
    params: object
    max_len: int = 512

    def __post_init__(self):
        self._servers: dict[int, InferenceServer] = {}

    def generate(self, prompts: np.ndarray, n_tokens: int = 16):
        """prompts: (B, S0) int32. Greedy continuation of n_tokens."""
        b = int(np.asarray(prompts).shape[0])
        server = self._servers.get(b)
        if server is None:
            server = InferenceServer(self.cfg, self.params,
                                     max_len=self.max_len, max_batch=b)
            self._servers[b] = server
        return server.generate(prompts,
                               SamplingParams(max_tokens=n_tokens))


# ---------------------------------------------------------------------------
# quantized mixed-precision serving of a discretized layer (paper Fig. 3)
# ---------------------------------------------------------------------------

def export_mixed_precision_layer(w: np.ndarray, channel_bits: np.ndarray,
                                 perm: np.ndarray | None = None):
    """w: (C_out, C_in) float weights; channel_bits: (C_out,) in {0,2,4,8}.

    Returns (packed_layers, perm, kept) where packed_layers is
    [(bits, wq_packed, scales), ...] in ascending-bits order after the
    Fig. 3 reordering; pruned (0-bit) channels are dropped entirely (a
    fully-pruned layer packs to an empty list with ``kept == 0``).
    ``perm`` overrides the reorder permutation (e.g. the one recorded in a
    :class:`~repro.api.plan.CompressionPlan`); by default it is recomputed
    from ``channel_bits``.  Packing is shared with the in-forward
    :class:`~repro.nn.quantized.PackedLinear` path, so per-layer exports
    and plan-driven decode are byte-identical.
    """
    return nnq.pack_channelwise(w, channel_bits, perm=perm)


def mixed_precision_matmul(x: jax.Array, packed_layers) -> jax.Array:
    """Serve y = x @ W^T for a reordered mixed-precision layer: one
    quant_matmul per precision group, outputs concatenated (Fig. 3).
    Activations are int8-quantized per row (batch-invariant); an empty
    ``packed_layers`` returns a zero-width (M, 0) result."""
    return nnq.mixed_precision_matmul(x, packed_layers)


def export_plan_layers(plan, weights: dict) -> dict:
    """Export every layer of a :class:`CompressionPlan` for serving.

    ``weights`` maps gamma-group name -> (C_out, C_in) float matrix (conv
    kernels reshaped to 2-D; for the LM, ``lm.serve_weight_groups``).
    Uses the plan's recorded per-group channel bits AND its stored Fig. 3
    permutations, so a saved+loaded plan packs byte-identically to the
    in-memory one. Returns {group: (packed_layers, perm, kept)}.
    """
    out = {}
    for grp, w in weights.items():
        if grp not in plan.channel_bits:
            raise KeyError(f"group {grp!r} is not in the plan "
                           f"(groups: {sorted(plan.channel_bits)})")
        out[grp] = export_mixed_precision_layer(
            np.asarray(w), plan.channel_bits[grp],
            perm=plan.permutations[grp])
    return out
