"""Unified cache backends for the serving stack.

The :class:`~repro.serve.engine.InferenceServer` no longer owns raw
KV/SSM buffers; it drives a :class:`CacheBackend`:

    alloc(uid, slot, n_prompt) -> CacheHandle     (admission)
    insert(handle, prefill_caches)                (prompt KV/SSM -> cache)
    append(handle)                                (one decoded token;
                                                   may allocate a page ->
                                                   raises PoolExhausted)
    gather() -> caches pytree                     (resident tree for
                                                   decode_step; donated)
    device_tables() -> (B, P) int32 | None        (paged: device-resident
                                                   block tables, NOT
                                                   donated; cached across
                                                   steps, updated
                                                   incrementally)
    commit(new_caches)                            (store the step's output)
    free(handle)                                  (retirement/preemption)
    can_admit(n_prompt) / memory_report()         (the admission contract)

Two implementations:

* :class:`DenseCache` -- the pre-existing behavior: one dense
  ``(nsb, max_batch, max_len, ...)`` buffer per KV tensor, every slot pins
  ``max_len`` positions regardless of actual length.
* :class:`PagedCache` -- vLLM-style paging (PagedAttention, Kwon et al.
  2023): a fixed pool of ``page_size``-token pages plus per-slot block
  tables; pages are allocated on admission (prompt + first decode write)
  and lazily as decode crosses page boundaries, and freed on retirement,
  so cache memory scales with tokens actually held.  SSM state is O(1)
  per request and lives in a parallel per-slot pool.  Physical page 0 is
  a reserved null page: inactive slots and unused block-table entries
  point at it, and anything written there is only ever read at masked
  positions.  Each KV pool is ``(nsb, n_pages + 1, page_size, hkv *
  hd)``: lane-dense pages (the layout a TPU holds without padding and
  the paged kernels read) for every layer at once.  A step addresses
  layer ``j``'s page ``t`` as page ``t + j * (n_pages + 1)`` of the
  flat pool (``lm.layer_tables``), so one page id backs a token in
  every layer, the tables here stay per slot, and no layer's pool is
  ever sliced out or restacked.

The backends' contract is *token-for-token invariance*: the same request
stream produces identical tokens on either backend (and solo vs.
batched).  ``page_size`` must divide ``max_len`` so a slot's pages cover
exactly the dense position range.

Decode reads the page pool IN PLACE: ``gather()`` returns the resident
pool tree (no per-step view materialization, no per-step host->device
table upload) and ``device_tables()`` the block tables, threaded through
``lm.decode_step`` outside the donated cache tree.  On TPU attention
runs the ``repro.kernels.paged_attention`` Pallas kernel over the pool;
off-TPU the fallback view is bitwise identical to the dense row.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm


class PoolExhausted(RuntimeError):
    """The page pool cannot serve an allocation; the engine reacts by
    preempting a request back to the queue."""


@dataclasses.dataclass
class CacheHandle:
    """One admitted request's cache residency."""

    uid: int
    slot: int                 # decode-batch row / block-table row
    n_tokens: int             # cache positions written so far
    pages: list = dataclasses.field(default_factory=list)


def _ins_slot(big, small, slot):
    """Insert a per-request state (leading batch dim 1) into slot row."""
    small = small.astype(big.dtype)
    starts = (0, slot) + (0,) * (big.ndim - 2)
    return jax.lax.dynamic_update_slice(big, small, starts)


# ---------------------------------------------------------------------------
# incremental device-side block-table updates
# ---------------------------------------------------------------------------
#
# The block tables live on device across decode steps (the decode step
# reads them as a non-donated argument); page-allocation events patch
# single entries via these jitted helpers instead of re-uploading the
# host table every step.  TRACE_COUNTS increments once per *trace* (not
# per call) -- the no-per-step-host-sync test asserts it stays flat while
# decode runs.

TRACE_COUNTS = collections.Counter()


def _counting_jit(name: str, fn):
    def traced(*args):
        TRACE_COUNTS[name] += 1          # python side effect: trace-time only
        return fn(*args)
    return jax.jit(traced)


_table_set_row = _counting_jit(
    "table_set_row", lambda t, slot, row: t.at[slot].set(row))
_table_set_entry = _counting_jit(
    "table_set_entry", lambda t, slot, pg, phys: t.at[slot, pg].set(phys))
_table_clear_row = _counting_jit(
    "table_clear_row", lambda t, slot: t.at[slot].set(0))


class CacheBackend:
    """Shared bookkeeping; subclasses fill in the storage strategy."""

    name = "abstract"

    def __init__(self, cfg, max_batch: int, max_len: int):
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.caches = None
        self._metrics = None

    # -- admission contract -------------------------------------------------
    def can_admit(self, n_prompt: int) -> bool:
        raise NotImplementedError

    def check_feasible(self, n_prompt: int, max_tokens: int):
        """Raise if the request could never run to completion alone."""

    def alloc(self, uid: int, slot: int, n_prompt: int) -> CacheHandle:
        raise NotImplementedError

    def free(self, handle: CacheHandle):
        raise NotImplementedError

    def append(self, handle: CacheHandle):
        """Advance one decoded token; ensure the next write position is
        backed by storage (may raise :class:`PoolExhausted`)."""
        handle.n_tokens += 1

    # -- data movement ------------------------------------------------------
    def insert(self, handle: CacheHandle, prefill_caches):
        raise NotImplementedError

    def gather(self):
        """The caches pytree ``lm.decode_step`` consumes this step."""
        return self.caches

    def device_tables(self):
        """Paged backends: the device-resident (B, P) block tables the
        decode step takes OUTSIDE the donated cache tree (None for
        backends that need none).  The engine truncates them to the
        live-page prefix INSIDE the jitted step (static width), so
        decode attention scans only pages some slot actually wrote."""
        return None

    def commit(self, new_caches):
        """Store the (donated-through) cache tree a decode step returned."""
        self.caches = new_caches

    # -- reporting ----------------------------------------------------------
    def memory_report(self) -> dict:
        raise NotImplementedError

    def bind_metrics(self, registry):
        """Attach a :class:`repro.obs.MetricsRegistry` (or None).  The
        engine calls this so ``publish_metrics`` and event counters have
        somewhere to write; instrumentation is host-side bookkeeping
        only -- cache data movement is untouched."""
        self._metrics = registry if (registry is not None
                                     and registry.enabled) else None

    def shrink_pool(self, n_pages: int) -> int:
        """Withhold up to ``n_pages`` free pages from the pool (the
        chaos layer's page-pool-pressure fault; a pure host-side
        bookkeeping change).  Returns how many were actually withheld
        (0 for backends without a pool)."""
        return 0

    def restore_pool(self) -> int:
        """Return every withheld page to the free pool; returns how
        many came back."""
        return 0

    def publish_metrics(self):
        """Mirror the numeric fields of :meth:`memory_report` into
        ``serve_cache_<key>{backend=...}`` gauges."""
        if self._metrics is None:
            return
        for key, value in self.memory_report().items():
            if isinstance(value, bool) or not isinstance(
                    value, (int, float)):
                continue
            self._metrics.gauge(
                f"serve_cache_{key}",
                f"Cache backend memory_report field {key!r}",
                labels=("backend",)).set(value, backend=self.name)

    def reset(self):
        """Drop all residency bookkeeping (buffers may keep stale data;
        every readable position is overwritten before it is unmasked)."""


class DenseCache(CacheBackend):
    """Current behavior, refactored behind the backend API: every decode
    slot pins a dense ``max_len`` KV row for its whole lifetime."""

    name = "dense"

    def __init__(self, cfg, max_batch: int, max_len: int):
        super().__init__(cfg, max_batch, max_len)
        self.caches = lm.init_caches(cfg, max_batch, max_len)
        self._bytes = lm.dense_cache_bytes(cfg, max_batch, max_len)
        self._live_tokens = 0
        self._peak_tokens = 0
        self._handles: dict[int, CacheHandle] = {}

        def ins(caches, pcaches, slot):
            return jax.tree.map(
                lambda big, small: _ins_slot(big, small, slot),
                caches, pcaches)

        self._insert = jax.jit(ins, donate_argnums=(0,))

    def can_admit(self, n_prompt: int) -> bool:
        return True

    def alloc(self, uid, slot, n_prompt):
        h = CacheHandle(uid=uid, slot=slot, n_tokens=n_prompt)
        self._handles[slot] = h
        self._live_tokens += n_prompt + 1
        self._peak_tokens = max(self._peak_tokens, self._live_tokens)
        return h

    def append(self, handle):
        handle.n_tokens += 1
        self._live_tokens += 1
        self._peak_tokens = max(self._peak_tokens, self._live_tokens)

    def free(self, handle):
        self._handles.pop(handle.slot, None)
        self._live_tokens -= handle.n_tokens + 1
        handle.pages = []

    def insert(self, handle, prefill_caches):
        self.caches = self._insert(self.caches, prefill_caches,
                                   jnp.asarray(handle.slot, jnp.int32))

    def memory_report(self) -> dict:
        return {
            "backend": self.name,
            "max_batch": self.max_batch,
            "max_len": self.max_len,
            "cache_bytes": self._bytes,
            "peak_cache_bytes": self._bytes,   # dense pins everything
            "live_tokens": self._live_tokens,
            "peak_live_tokens": self._peak_tokens,
            "gather_transient_bytes": 0,       # gather() is the resident tree
        }

    def reset(self):
        self._handles.clear()
        self._live_tokens = 0
        self._peak_tokens = 0


class PagedCache(CacheBackend):
    """Fixed-size page pool + per-request block tables.

    ``n_pages`` usable pages of ``page_size`` tokens each (plus the
    reserved null page 0).  Admission requires pages covering the prompt
    AND the first decode write, with ``reserve_pages`` extra free as the
    admission reservation; decode allocates lazily on page-boundary
    crossings via :meth:`append`.
    """

    name = "paged"

    def __init__(self, cfg, max_batch: int, max_len: int, *,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 reserve_pages: int = 1):
        super().__init__(cfg, max_batch, max_len)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size:
            raise ValueError(
                f"page_size must divide max_len for dense-equivalent "
                f"attention views, got page_size={page_size} "
                f"max_len={max_len}")
        self.page_size = int(page_size)
        self.table_width = max_len // page_size
        if n_pages is None:        # dense-equivalent capacity
            n_pages = max_batch * self.table_width
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        self.n_pages = int(n_pages)
        self.reserve_pages = max(int(reserve_pages), 0)

        self.caches = lm.init_paged_caches(cfg, max_batch, self.page_size,
                                           self.n_pages)
        self._has_kv = any("kv" in c for c in self.caches.values())
        self._table = np.zeros((max_batch, self.table_width), np.int32)
        # device-resident copy of the block tables: uploaded once here,
        # then patched incrementally on admission / page allocation /
        # free -- decode steps reuse the SAME device array (no per-step
        # host->device sync; `table_host_uploads` counts full-row
        # uploads, which only happen at admission frequency)
        self._table_dev = jnp.asarray(self._table)
        self.table_host_uploads = 0
        self._free = collections.deque(range(1, self.n_pages + 1))
        self._withheld: list = []     # pages removed by shrink_pool()
        self._handles: dict[int, CacheHandle] = {}
        self._peak_pages = 0

        self.bytes_per_page = lm.kv_bytes_per_page(cfg, self.page_size)
        self.ssm_slot_bytes = lm.ssm_bytes_per_slot(cfg)
        self.dense_equivalent_bytes = lm.dense_cache_bytes(
            cfg, max_batch, max_len)

        def ins_mamba(mstates, pstates, slot):
            return jax.tree.map(
                lambda big, small: _ins_slot(big, small, slot),
                mstates, pstates)

        self._insert_mamba = jax.jit(ins_mamba, donate_argnums=(0,))

    # -- page arithmetic ----------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        if not self._has_kv:
            return 0               # pure-SSM: state is per-slot, no pages
        return -(-max(n_tokens, 0) // self.page_size)

    # -- admission contract -------------------------------------------------
    def _admission_pages(self, n_prompt: int) -> int:
        """Pages covering the prompt + the first decode write (clamped to
        the table width, mirroring :meth:`append`'s max_len clamp)."""
        return self.pages_for(min(n_prompt + 1, self.max_len))

    def can_admit(self, n_prompt: int) -> bool:
        need = self._admission_pages(n_prompt) + self.reserve_pages
        return len(self._free) >= need

    def check_feasible(self, n_prompt: int, max_tokens: int):
        total = min(n_prompt + max_tokens, self.max_len)
        need = self.pages_for(total) + self.reserve_pages
        if need > self.n_pages:
            raise ValueError(
                f"request needs {need} pages (prompt {n_prompt} + "
                f"max_tokens {max_tokens} + reserve {self.reserve_pages}) "
                f"but the pool only has {self.n_pages}; it could never be "
                f"admitted")

    def bind_metrics(self, registry):
        super().bind_metrics(registry)
        if self._metrics is not None:
            # pre-create so the series exists (at 0) even in runs that
            # never exhaust the pool
            self._metrics.counter(
                "serve_pool_exhausted_total",
                "Page-pool allocation failures (each triggers a "
                "preemption in the engine)").inc(0)
            self._gauge_pages()

    def _count_exhausted(self):
        if self._metrics is not None:
            self._metrics.counter("serve_pool_exhausted_total").inc()

    def _gauge_pages(self):
        if self._metrics is not None:
            self._metrics.gauge(
                "serve_pages_in_use",
                "Pages currently allocated out of the pool").set(
                self.pages_in_use)

    def alloc(self, uid, slot, n_prompt):
        n = self._admission_pages(n_prompt)
        if len(self._free) < n:
            self._count_exhausted()
            raise PoolExhausted(
                f"need {n} pages for uid {uid}, {len(self._free)} free")
        h = CacheHandle(uid=uid, slot=slot, n_tokens=n_prompt,
                        pages=[self._free.popleft() for _ in range(n)])
        self._table[slot] = 0
        self._table[slot, :n] = h.pages
        self._table_dev = _table_set_row(self._table_dev, slot,
                                         jnp.asarray(self._table[slot]))
        self.table_host_uploads += 1
        self._handles[slot] = h
        self._note_usage()
        return h

    def append(self, handle):
        # back the next write position BEFORE advancing the counter: a
        # PoolExhausted raise leaves the handle untouched, so the
        # engine's preempt-and-retry loop can safely call append again
        nxt = handle.n_tokens + 1       # next cache write position
        if nxt < self.max_len and self._has_kv:
            pg = nxt // self.page_size
            if pg >= len(handle.pages):
                if not self._free:
                    self._count_exhausted()
                    raise PoolExhausted(
                        f"uid {handle.uid} needs page {pg}, pool empty")
                phys = self._free.popleft()
                handle.pages.append(phys)
                self._table[handle.slot, pg] = phys
                self._table_dev = _table_set_entry(self._table_dev,
                                                   handle.slot, pg, phys)
                self._note_usage()
        handle.n_tokens += 1

    def free(self, handle):
        self._free.extend(handle.pages)
        handle.pages = []
        self._table[handle.slot] = 0
        self._table_dev = _table_clear_row(self._table_dev, handle.slot)
        self._handles.pop(handle.slot, None)
        self._gauge_pages()

    def _note_usage(self):
        self._peak_pages = max(self._peak_pages, self.pages_in_use)
        self._gauge_pages()

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free) - len(self._withheld)

    def shrink_pool(self, n_pages: int) -> int:
        # withhold from the BACK of the free deque so page-id reuse
        # order for live traffic is unchanged until pressure actually
        # bites (determinism: same fault -> same allocation sequence)
        taken = 0
        while taken < int(n_pages) and self._free:
            self._withheld.append(self._free.pop())
            taken += 1
        self._gauge_pages()
        return taken

    def restore_pool(self) -> int:
        n = len(self._withheld)
        # restore in reverse so the free deque returns to its
        # pre-pressure ordering
        while self._withheld:
            self._free.append(self._withheld.pop())
        self._gauge_pages()
        return n

    # -- data movement ------------------------------------------------------
    def kv_caches(self):
        """The KV-pool subtree ``{layer: {"kv": {"k","v"}}}`` to hand to
        (and have donated by) the engine's paged prefill step; layers
        without attention are absent.  Empty for pure-SSM stacks.  After
        the step runs, the pools referenced here are dead (donated) until
        :meth:`insert` commits the step's outputs."""
        return {ln: {"kv": c["kv"]} for ln, c in self.caches.items()
                if "kv" in c}

    def insert(self, handle, prefill_caches):
        """Commit one admitted request's prefill state.

        KV leaves of ``prefill_caches`` are the page POOLS returned by
        the engine's paged prefill step -- the prompt K/V was already
        scattered into this request's pages inside the jit, with the old
        pools donated, so committing them is a pointer swap (no dense
        round-trip, no per-admission scatter dispatch).  SSM leaves are
        per-slot ``(nsb, 1, ...)`` prefill states, scattered into the
        slot's row of the state tree."""
        for lname, c in self.caches.items():
            pc = prefill_caches.get(lname) or {}
            if "kv" in c and "kv" in pc:
                c["kv"] = pc["kv"]
        m_big = {ln: c["mamba"] for ln, c in self.caches.items()
                 if "mamba" in c}
        if m_big:
            m_small = {ln: prefill_caches[ln]["mamba"] for ln in m_big}
            m_new = self._insert_mamba(m_big, m_small,
                                       jnp.asarray(handle.slot, jnp.int32))
            for ln, st in m_new.items():
                self.caches[ln]["mamba"] = st

    def device_tables(self):
        # the SAME device array across steps (it rides outside the
        # donated cache tree); only admission / page-boundary / free
        # events replace it, via the incremental jitted updaters above
        return self._table_dev

    # -- reporting ----------------------------------------------------------
    def memory_report(self) -> dict:
        in_use = self.pages_in_use
        slots = len(self._handles)
        return {
            "backend": self.name,
            "page_size": self.page_size,
            "n_pages": self.n_pages,
            "pages_in_use": in_use,
            "pages_free": len(self._free),
            "pages_withheld": len(self._withheld),
            "peak_pages_in_use": self._peak_pages,
            "bytes_per_page": self.bytes_per_page,
            "ssm_slot_bytes": self.ssm_slot_bytes,
            "cache_bytes_in_use": in_use * self.bytes_per_page
            + slots * self.ssm_slot_bytes,
            "peak_cache_bytes": self._peak_pages * self.bytes_per_page
            + self.max_batch * self.ssm_slot_bytes,
            "pool_bytes": (self.n_pages + 1) * self.bytes_per_page
            + self.max_batch * self.ssm_slot_bytes,
            "dense_equivalent_bytes": self.dense_equivalent_bytes,
            # decode reads the pool in place (paged-attention kernel /
            # bitwise-equivalent fallback view); no dense-width
            # (max_batch, max_len) KV transient is materialized per step
            "gather_transient_bytes": 0,
            "table_bytes": int(self._table_dev.size
                               * self._table_dev.dtype.itemsize),
            "table_host_uploads": self.table_host_uploads,
        }

    def reset(self):
        for h in list(self._handles.values()):
            self.free(h)
        self._table[:] = 0
        self._table_dev = jnp.asarray(self._table)
        self.table_host_uploads = 0
        self._free = collections.deque(range(1, self.n_pages + 1))
        self._withheld = []
        self._peak_pages = 0


def make_backend(kind: str, cfg, max_batch: int, max_len: int,
                 **kwargs) -> CacheBackend:
    """``kind``: "dense" | "paged" (kwargs: page_size, n_pages,
    reserve_pages)."""
    if kind == "dense":
        if kwargs:
            raise ValueError(f"DenseCache takes no options, got "
                             f"{sorted(kwargs)}")
        return DenseCache(cfg, max_batch, max_len)
    if kind == "paged":
        return PagedCache(cfg, max_batch, max_len, **kwargs)
    raise ValueError(f"unknown cache backend {kind!r} "
                     f"(expected 'dense' or 'paged')")
