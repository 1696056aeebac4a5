"""Serving launcher: plan-driven continuous-batching decode for any --arch
(reduced configs on CPU; the same prefill/decode step functions lower on
the production mesh in the dry-run).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b-smoke \
        --requests 4 --tokens 16

    # quantized decode from a saved CompressionPlan (or the built-in demo
    # plan), sampled at temperature 0.8, requests arriving over time:
    PYTHONPATH=src python -m repro.launch.serve --plan demo \
        --temperature 0.8 --top-k 40 --stream --arrival-gap 3

    # paged KV cache (vLLM-style page pool + block tables): cache memory
    # scales with live tokens; admission is memory-aware, the pool
    # preempts to the queue on exhaustion:
    PYTHONPATH=src python -m repro.launch.serve --plan demo \
        --cache paged --page-size 8 --pages 24 --stream
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch import compile_cache
from repro.models import lm
from repro.serve import engine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Request


def _load_plan(spec: str, cfg, params):
    if spec == "demo":
        return engine.synthetic_plan(cfg, params, bits=None, seed=0)
    from repro.api.plan import CompressionPlan
    return CompressionPlan.load(spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b-smoke")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (requests beyond this queue)")
    ap.add_argument("--plan", default=None,
                    help="CompressionPlan stem/path for quantized decode, "
                         "or 'demo' for a synthetic mixed-precision plan")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="streaming-arrivals mode: requests join the "
                         "queue over time instead of all at step 0")
    ap.add_argument("--arrival-gap", type=int, default=2,
                    help="decode steps between arrivals with --stream")
    ap.add_argument("--cache", default="dense",
                    choices=["dense", "paged"],
                    help="cache backend: dense slot buffers or a paged "
                         "pool with block tables + memory-aware admission")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page (must divide --max-len)")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size (default: dense-equivalent "
                         "max_batch*max_len/page_size)")
    ap.add_argument("--host-sampling", action="store_true",
                    help="sample on the host per token instead of the "
                         "on-device batched gumbel top-k path")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable observability and write the metrics "
                         "registry in Prometheus text format to PATH")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable observability and write the per-request "
                         "lifecycle trace as JSON lines to PATH")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = registry.get(args.arch)
    params = lm.init_params(cfg, jax.random.key(0))
    plan = None
    if args.plan is not None:
        plan = _load_plan(args.plan, cfg, params)
        print(f"[serve] quantized decode: {plan.summary()}")
    obs = None
    if args.metrics or args.trace:
        from repro.obs import Observability
        obs = Observability()
    server = engine.InferenceServer(cfg, params, plan=plan,
                                    max_len=args.max_len,
                                    max_batch=args.max_batch,
                                    cache=args.cache,
                                    page_size=args.page_size,
                                    pages=args.pages,
                                    sample_on_device=not args.host_sampling,
                                    obs=obs)

    rng = np.random.default_rng(0)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        max_tokens=args.tokens, seed=args.seed)
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=args.prompt_len).astype(np.int32)
        arrival = i * args.arrival_gap if args.stream else 0
        reqs.append(Request(uid=i, prompt=prompt, sampling=sp,
                            arrival=arrival))

    t0 = time.time()
    out = server.serve(reqs)
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    mode = "stream" if args.stream else "batch"
    quant = "quantized" if plan is not None else "float"
    print(f"[serve] {args.requests} requests x {args.tokens} tokens "
          f"({mode}, {quant}, {args.cache} cache) in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, {server.stats['decode_steps']} decode "
          f"steps, {server.stats['preemptions']} preemptions)")
    mem = server.stats["memory"]
    if mem["backend"] == "paged":
        print(f"[serve] memory: peak {mem['peak_cache_bytes']} B "
              f"({mem['peak_pages_in_use']}/{mem['n_pages']} pages of "
              f"{mem['bytes_per_page']} B) vs dense-equivalent "
              f"{mem['dense_equivalent_bytes']} B")
    else:
        print(f"[serve] memory: dense cache {mem['cache_bytes']} B "
              f"(pinned for the full serve)")
    for i in range(min(args.requests, 4)):
        print(f"  req{i}: prompt={[int(t) for t in reqs[i].prompt[:6]]}... "
              f"completion={[int(t) for t in out[i][:8]]}")

    if obs is not None:
        from repro.obs import write_prometheus, write_trace
        summary = server.metrics_snapshot().get("summary", {})
        if summary:
            ttft = summary["ttft_s"]
            tok = summary["token_latency_s"]
            fmt = lambda v: "n/a" if v is None else f"{v * 1e3:.1f}ms"
            print(f"[obs] ttft p50={fmt(ttft['p50'])} "
                  f"p95={fmt(ttft['p95'])} p99={fmt(ttft['p99'])} | "
                  f"token p50={fmt(tok['p50'])} p95={fmt(tok['p95'])} "
                  f"p99={fmt(tok['p99'])} | "
                  f"preemptions={summary['preemptions']} "
                  f"pages_hwm={summary['pages_held_hwm']}")
            widths = summary.get("decode_compiles_per_width")
            if widths:
                print(f"[obs] decode compiles per width: {widths}")
        if args.metrics:
            write_prometheus(obs.registry, args.metrics)
            print(f"[obs] metrics -> {args.metrics}")
        if args.trace:
            write_trace(obs.tracer, args.trace)
            print(f"[obs] trace -> {args.trace} "
                  f"({len(obs.tracer.events)} events)")


if __name__ == "__main__":
    main()
