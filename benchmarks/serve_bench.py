"""Serving throughput benchmark: batched continuous-batching decode,
float vs. plan-quantized at 2/4/8-bit (and a mixed) precision, dense vs.
paged cache backends.

Emits ``BENCH_serve.json`` (the serving-benchmark trajectory format; each
entry is one serving variant with its measured decode throughput and its
cache backend's peak memory) and prints the orchestrator's
``name,us_per_call,derived`` CSV lines.

The dense-vs-paged pairs run the SAME streaming mixed-prompt-length
workload and must produce identical tokens (asserted); the paged rows
additionally record peak cache bytes, which scale with live tokens
instead of the dense ``max_batch * max_len`` pin.

Since PR 10 every row also carries a **prefill-latency split**
(``prefill_ms_p50/p95/p99``: tracer-measured admitted->prefilled wall
per admission), and a ``prefill-bucketed-baseline`` row reconstructs the
retired pre-PR 10 admission path (prompt padded to a page-count bucket,
dense flash prefill, then the ``_scatter_pages`` round-trip of dense KV
into pool pages) on the same workload lengths -- the paged row's
``prefill_vs_bucketed`` block records the TTFT delta and asserts the
paged path is no slower.

    PYTHONPATH=src python -m benchmarks.serve_bench [--arch ...] \
        [--out BENCH_serve.json]

Defaults are sized for a 1-core CPU (the quantized path runs the Pallas
kernel in interpret mode there; on TPU the same code hits the MXU int8
kernel, which is where the quantized-vs-float gap becomes a win rather
than an overhead).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

import jax.numpy as jnp

from repro.configs import registry
from repro.launch import steps
from repro.models import lm
from repro.obs import Observability, percentiles
from repro.serve import cache as cache_mod
from repro.serve import engine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Request

SCHEMA_VERSION = 5


def machine_baseline(repeats=5, n=50, dim=256):
    """Fixed-work calibration row: a seeded float32 matmul chain whose
    wall time depends only on host speed.  Cross-PR ``BENCH_serve.json``
    deltas divide by this row's ``wall_s`` before being read as code
    regressions -- the PR 4->5 7088->3659 tok/s swing was machine speed
    (per ROADMAP), which this row makes quantifiable."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((dim, dim)).astype(np.float32)
    b = rng.standard_normal((dim, dim)).astype(np.float32)
    wall = float("inf")
    for _ in range(repeats):
        x = a
        t0 = time.time()
        for _ in range(n):
            x = x @ b
            x = x / np.float32(np.abs(x).max() + 1.0)   # stay finite
        wall = min(wall, time.time() - t0)
    return {"name": "machine_baseline", "cache": None,
            "matmul_chain": {"dim": dim, "n": n},
            "wall_s": round(wall, 5),
            "matmul_gflops": round(2 * n * dim**3 / wall / 1e9, 2),
            "plan": None}


def make_requests(cfg, n, prompt_lens, tokens, gap):
    """Streaming arrivals with mixed prompt lengths (the paged backend's
    target workload)."""
    rng = np.random.default_rng(0)
    sp = SamplingParams(max_tokens=tokens)        # greedy: deterministic
    return [Request(uid=i,
                    prompt=rng.integers(
                        0, cfg.vocab,
                        size=prompt_lens[i % len(prompt_lens)]
                    ).astype(np.int32),
                    sampling=sp, arrival=gap * i)
            for i in range(n)]


def _row_from(stats, name, cache, wall, out, plan):
    """Build one result row from a serve() stats snapshot.  `stats` must
    come from the SAME repeat as `wall` (the best one), or the row would
    describe a different run than the wall time."""
    tokens = int(sum(len(r) for r in out.values()))
    mem = stats["memory"]
    row = {
        "name": name,
        "cache": cache,
        "tokens": tokens,
        "wall_s": round(wall, 4),
        "tok_per_s": round(tokens / wall, 2),
        "decode_steps": stats["decode_steps"],
        "preemptions": stats["preemptions"],
        "peak_cache_bytes": mem["peak_cache_bytes"]
        if cache == "paged" else mem["cache_bytes"],
        "plan": None,
    }
    if cache == "paged":
        row["page_size"] = mem["page_size"]
        row["n_pages"] = mem["n_pages"]
        row["peak_pages_in_use"] = mem["peak_pages_in_use"]
        row["dense_equivalent_bytes"] = mem["dense_equivalent_bytes"]
    if plan is not None:
        row["plan"] = {
            "groups": len(plan.channel_bits),
            "prune_fraction": round(plan.prune_fraction(), 4),
            "meta_bits": plan.meta.get("bits"),
        }
    return row, out


def _prefill_latencies(tracer):
    """Seconds from admission to prefill-complete, one entry per
    admission (a preempted request's re-prefill counts again)."""
    t_adm: dict = {}
    out = []
    for ev in tracer.events:
        if ev.kind == "admitted":
            t_adm[ev.uid] = ev.t
        elif ev.kind == "prefilled" and ev.uid in t_adm:
            out.append(ev.t - t_adm.pop(ev.uid))
    return out


def _add_latency_split(row, server, requests, wall, repeats=3):
    """Per-request latency split from the request tracer.

    Attaches a fresh Observability bundle to the already-warmed server
    (host-side only: no recompiles -- ``attach_obs`` never touches the
    jitted closures), re-runs the workload best-of-N, and folds the
    tracer's TTFT / per-token percentiles into the row.  The traced wall
    vs. the untraced ``wall`` is the measured obs overhead, reported as
    ``obs_overhead_pct`` per the acceptance criterion that the default
    (obs-off) path stays at baseline while the obs-on cost is known.
    """
    obs = Observability()
    server.attach_obs(obs)
    try:
        traced_wall = float("inf")
        for _ in range(repeats):
            t0 = time.time()
            server.serve(requests)
            traced_wall = min(traced_wall, time.time() - t0)
        ttft = percentiles(obs.tracer.ttfts())
        tok = percentiles(obs.tracer.token_latencies())
        pre = percentiles(_prefill_latencies(obs.tracer))
        for p in ("p50", "p95", "p99"):
            row[f"ttft_ms_{p}"] = round(ttft[p] * 1e3, 3)
            row[f"token_ms_{p}"] = round(tok[p] * 1e3, 3)
            row[f"prefill_ms_{p}"] = round(pre[p] * 1e3, 3)
        row["obs_overhead_pct"] = round(
            (traced_wall - wall) / wall * 100.0, 2)
    finally:
        server.attach_obs(None)
    return row


def bench_variant(name, cfg, params, plan, requests, max_len, max_batch,
                  repeats=3):
    """Single dense-backend variant (paged rows go through
    :func:`bench_pair`, which measures the backends interleaved)."""
    server = engine.InferenceServer(cfg, params, plan=plan,
                                    max_len=max_len, max_batch=max_batch)
    server.serve(requests)                # compile + warm caches
    wall = float("inf")                   # best-of-N: the wall times are
    for _ in range(repeats):              # tens of ms, CPU noise is not
        t0 = time.time()                  # (identical tokens every run)
        out = server.serve(requests)
        w = time.time() - t0
        if w < wall:
            wall, stats = w, server.stats
    row, out = _row_from(stats, name, "dense", wall, out, plan)
    _add_latency_split(row, server, requests, wall)
    return row, out


def bench_pair(name, cfg, params, plan, requests, max_len, max_batch,
               page_size, repeats=5):
    """Dense vs. paged on the SAME workload, measured INTERLEAVED
    (dense, paged, dense, paged, ...) with best-of-N walls, so drifting
    background load on the benchmark host hits both variants alike.
    Token streams are asserted identical.

    The paged server gets a pool of HALF the dense-equivalent capacity
    -- the memory-bounded deployment point paging exists for (dense
    cannot run below ``max_batch * max_len`` at all); the default
    workload's peak fits without preemption (recorded in the row)."""
    pages = (max_batch * max_len // page_size) // 2
    dense = engine.InferenceServer(cfg, params, plan=plan,
                                   max_len=max_len, max_batch=max_batch)
    paged = engine.InferenceServer(cfg, params, plan=plan,
                                   max_len=max_len, max_batch=max_batch,
                                   cache="paged", page_size=page_size,
                                   pages=pages)
    dense.serve(requests)
    paged.serve(requests)
    wall_d = wall_p = float("inf")
    for _ in range(repeats):
        t0 = time.time()
        out_d = dense.serve(requests)
        w = time.time() - t0
        if w < wall_d:
            wall_d, stats_d = w, dense.stats
        t0 = time.time()
        out_p = paged.serve(requests)
        w = time.time() - t0
        if w < wall_p:
            wall_p, stats_p = w, paged.stats
    for uid in out_d:
        np.testing.assert_array_equal(out_d[uid], out_p[uid])
    row_d, _ = _row_from(stats_d, name, "dense", wall_d, out_d, plan)
    row_p, _ = _row_from(stats_p, f"{name}-paged", "paged", wall_p,
                         out_p, plan)
    _add_latency_split(row_d, dense, requests, wall_d)
    _add_latency_split(row_p, paged, requests, wall_p)
    return row_d, row_p


def bucketed_prefill_baseline(cfg, params, prompt_lens, n_requests,
                              max_len, max_batch, page_size, repeats=10):
    """Per-admission prefill wall, measured identically for both paths.

    - **bucketed** reconstructs the retired pre-PR 10 admission path:
      prompt padded on the host to a page-count bucket, dense flash
      prefill at the bucket length (one compile per bucket), then the
      ``_scatter_pages`` round-trip writing the dense per-layer KV into
      pool pages via a separately dispatched jit.
    - **paged** is the live engine admission (``_run_prefill``: pad to
      a q-chunk multiple, one pool-donating jit reading the page pool
      in place, pointer-swap insert).

    Both timed loops include the per-admission host work (padding,
    operand preparation, dispatch) -- that is what an admission costs
    in TTFT.  Returns the baseline row carrying both measurements."""
    n_pages = (max_batch * max_len // page_size) // 2
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, size=max_len).astype(np.int32)

    # --- retired bucketed path, reconstructed -------------------------
    backend = cache_mod.make_backend(
        "paged", cfg, max_batch, max_len, page_size=page_size,
        n_pages=n_pages)
    pools = {ln: c["kv"] for ln, c in backend.caches.items() if "kv" in c}
    prefill = jax.jit(steps.make_prefill_step(cfg))

    def scatter(pools, dense_kv, pages):
        # leaves are (n_sb, B=1, spad, hkv, hd) dense vs.
        # (n_sb, n_pages + 1, page_size, hkv, hd) pool
        def put(pool, kv):
            n = pages.shape[0]
            return pool.at[:, pages].set(
                kv[:, 0].reshape(kv.shape[0], n, page_size,
                                 *kv.shape[3:]).astype(pool.dtype))
        return jax.tree.map(put, pools, dense_kv)

    scatter_j = jax.jit(scatter, donate_argnums=(0,))
    per_len = {}
    for s in sorted(set(prompt_lens)):
        spad = -(-s // page_size) * page_size          # page bucket
        best = float("inf")
        for i in range(repeats + 1):                   # first = compile
            t0 = time.time()
            padded = np.zeros(spad, np.int32)          # host bucket pad
            padded[:s] = toks[:s]
            logits, pc = prefill(params,
                                 {"tokens": jnp.asarray(padded)[None]})
            pages = jnp.arange(1, spad // page_size + 1,
                               dtype=jnp.int32)
            pools = scatter_j(
                pools, {ln: pc[ln]["kv"] for ln in pools}, pages)
            jax.block_until_ready((logits, pools))
            if i > 0:
                best = min(best, time.time() - t0)
        per_len[s] = best

    # --- live paged admission (the engine's _run_prefill) -------------
    srv = engine.InferenceServer(cfg, params, max_len=max_len,
                                 max_batch=max_batch, cache="paged",
                                 page_size=page_size, pages=n_pages)
    srv.begin()
    pbackend = srv.backend
    per_len_paged = {}
    for s in sorted(set(prompt_lens)):
        handle = pbackend.alloc(uid=s, slot=0, n_prompt=s)
        best = float("inf")
        for i in range(repeats + 1):
            t0 = time.time()
            logits = srv._run_prefill(pbackend, handle, toks[:s])
            jax.block_until_ready(logits)
            if i > 0:
                best = min(best, time.time() - t0)
        pbackend.free(handle)
        per_len_paged[s] = best

    # replicate per-admission walls to the workload's composition so the
    # percentiles describe the default workload's admission mix
    def mix(per):
        return percentiles([per[prompt_lens[i % len(prompt_lens)]]
                            for i in range(n_requests)])

    pre, pre_paged = mix(per_len), mix(per_len_paged)
    row = {"name": "prefill-bucketed-baseline", "cache": "paged",
           "page_size": page_size,
           "prefill_us_per_admission": {
               str(s): round(w * 1e6, 1) for s, w in per_len.items()},
           "paged_prefill_us_per_admission": {
               str(s): round(w * 1e6, 1)
               for s, w in per_len_paged.items()},
           "plan": None}
    for p in ("p50", "p95", "p99"):
        row[f"prefill_ms_{p}"] = round(pre[p] * 1e3, 3)
        row[f"paged_prefill_ms_{p}"] = round(pre_paged[p] * 1e3, 3)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    # decode-weighted default: this is a decode-throughput benchmark (the
    # admission path amortizes over the generated tokens, as in serving)
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--arrival-gap", type=int, default=2)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    cfg = registry.get(args.arch)
    params = lm.init_params(cfg, jax.random.key(0))
    prompt_lens = (6, 14, 9, 21)
    requests = make_requests(cfg, args.requests, prompt_lens, args.tokens,
                             args.arrival_gap)

    variants = [("float", None)]
    for bits in (8, 4, 2):
        variants.append((f"quant-w{bits}",
                         engine.synthetic_plan(cfg, params, bits=bits)))
    variants.append(("quant-mixed",
                     engine.synthetic_plan(cfg, params, bits=None, seed=0)))

    base = machine_baseline()
    results = [base]
    print(f"serve/machine_baseline,{base['wall_s'] * 1e6:.0f},"
          f"matmul_gflops={base['matmul_gflops']}")
    for name, plan in variants:
        # paged counterpart for the trajectory headliners only (float +
        # mixed plan): same workload, identical tokens (asserted inside
        # bench_pair), interleaved measurement, paged memory recorded
        if name in ("float", "quant-mixed"):
            row, prow = bench_pair(name, cfg, params, plan, requests,
                                   args.max_len, args.max_batch,
                                   args.page_size)
            results += [row, prow]
            if name == "float":
                brow = bucketed_prefill_baseline(
                    cfg, params, prompt_lens, args.requests,
                    args.max_len, args.max_batch, args.page_size)
                # both sides of the delta come from the baseline row's
                # direct per-admission harness (same timing discipline);
                # prow's own prefill_ms_* stays tracer-measured in situ
                prow["prefill_vs_bucketed"] = {
                    "bucketed_ms_p50": brow["prefill_ms_p50"],
                    "paged_ms_p50": brow["paged_prefill_ms_p50"],
                    "ttft_delta_ms": {
                        p: round(brow[f"paged_prefill_ms_{p}"]
                                 - brow[f"prefill_ms_{p}"], 3)
                        for p in ("p50", "p95", "p99")},
                }
                assert (brow["paged_prefill_ms_p50"]
                        <= brow["prefill_ms_p50"]), \
                    ("paged prefill slower than the bucketed baseline: "
                     f"{brow['paged_prefill_ms_p50']} > "
                     f"{brow['prefill_ms_p50']} ms")
                results.append(brow)
                print(f"serve/prefill-bucketed-baseline,"
                      f"{brow['prefill_ms_p50'] * 1e3:.0f},"
                      f"paged_prefill_ms_p50="
                      f"{brow['paged_prefill_ms_p50']}")
            print(f"serve/{name},{row['wall_s'] * 1e6:.0f},"
                  f"tok_per_s={row['tok_per_s']}")
            print(f"serve/{prow['name']},{prow['wall_s'] * 1e6:.0f},"
                  f"tok_per_s={prow['tok_per_s']},"
                  f"peak_cache_bytes={prow['peak_cache_bytes']},"
                  f"dense_bytes={prow['dense_equivalent_bytes']}")
            continue
        row, _ = bench_variant(name, cfg, params, plan, requests,
                               args.max_len, args.max_batch)
        results.append(row)
        print(f"serve/{name},{row['wall_s'] * 1e6:.0f},"
              f"tok_per_s={row['tok_per_s']}")

    report = {
        "benchmark": "serve",
        "schema_version": SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "arch": cfg.name,
        "config": {"requests": args.requests,
                   "prompt_lens": list(prompt_lens),
                   "tokens": args.tokens,
                   "max_batch": args.max_batch,
                   "max_len": args.max_len,
                   "page_size": args.page_size,
                   "arrival_gap": args.arrival_gap},
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[serve_bench] wrote {args.out}")


if __name__ == "__main__":
    main()
