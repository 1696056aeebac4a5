"""Chip smoke test: the serve and search paths end to end on one TPU.

    python chip_smoke.py

Runs, in one process, through the entry points a user calls:

* kernel parity -- paged decode and paged prefill against the gathered
  view, and ``quant_matmul`` at w8/w4/w2 against its integer oracle, at
  the serve phase's shapes;
* serve -- ``llama3.2-1b`` at full width (16 layers, d_model 2048,
  32/8 heads of 64, vocab 128256, its own float32 weights, random from
  a seed) behind ``InferenceServer(cache="paged")``: four greedy
  requests of 40-208 prompt tokens, 16 new tokens each, once in float
  and once under a seeded mixed 0/2/4/8-bit plan;
* search -- the paper's ResNet-9 (width 16) on CIFAR-10-shaped synthetic
  data, a few steps each of Warmup, JointSearch and Finetune, ending in
  a ``CompressionPlan``.

Every check raises on failure.  Earlier lines print each phase's wall
and compile time and each parity error; the last line is one JSON object
naming the device.  Without a TPU the script exits 2 and prints no
result.  The persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import contextlib
import functools
import json
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro import api                                       # noqa: E402
from repro.configs import registry                          # noqa: E402
from repro.data import synthetic                            # noqa: E402
from repro.kernels.paged_attention import ops as pops       # noqa: E402
from repro.kernels.quant_matmul import ops as qops          # noqa: E402
from repro.kernels.quant_matmul import ref as qref          # noqa: E402
from repro.launch import compile_cache                      # noqa: E402
from repro.models import cnn, lm                            # noqa: E402
from repro.serve import engine                              # noqa: E402
from repro.serve.sampling import SamplingParams             # noqa: E402
from repro.serve.scheduler import Request                   # noqa: E402

SEED = 0
ARCH = "llama3.2-1b"
PROMPT_LENS = (40, 48, 200, 208)   # two are not a page multiple
NEW_TOKENS = 16
PAGE_SIZE = 16
MAX_LEN = 256
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def check(cond, what: str):
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileLog:
    """Backend compile time and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.secs, self.n, self.hits, self.misses = 0.0, 0, 0, 0
        self.each = []               # (secs, function name) per compile
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="?", **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.n += 1
            self.each.append((secs, fun_name))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.n, self.hits, self.misses


@contextlib.contextmanager
def phase(name: str, log: CompileLog):
    print(f"[{name}] start", flush=True)
    t0, c0, i0 = time.perf_counter(), log.snapshot(), len(log.each)
    yield
    secs, n, hits, misses = (b - a for a, b in zip(c0, log.snapshot()))
    slowest = ", ".join(f"{f} {s:.2f} s"
                        for s, f in sorted(log.each[i0:], reverse=True)[:3])
    print(f"[{name}] wall {time.perf_counter() - t0:.2f} s; compile "
          f"{secs:.2f} s in {n} compiles (persistent cache: {hits} hits, "
          f"{misses} misses); slowest compiles: {slowest}", flush=True)


def check_close(name, got, want, tol, why, relative=False):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                   f"{want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want)))
    if relative:
        err /= max(float(np.max(np.abs(want))), 1e-30)
    kind = "max rel err" if relative else "max abs err"
    print(f"  parity {name}: {kind} {err:.3e} (tol {tol:.0e}: {why})",
          flush=True)
    check(err <= tol, f"{name}: {kind} {err:.3e} > {tol:.0e}")


# the view's einsums run at "highest" precision, so they are the f32
# reference; a Mosaic f32 dot may round its operands to bf16 (8 mantissa
# bits, ~4e-3 relative in each score), which moves a softmax-weighted
# mean of N(0, 1) values by up to ~1e-2
ATTN_TOL = 2e-2
ATTN_WHY = "kernel f32 dots may take one bf16 pass"
# integer partial sums are exact in f32 below 2^24; only the two scale
# multiplies round
QMM_TOL = 1e-6
QMM_WHY = "int sums exact in f32, scales round once"


def paged_case(rng, lens, hkv, hd, n_pages, width):
    """A bf16 page pool of lane-dense pages holding ``lens`` tokens per
    slot under a random physical layout, as the serve phase's cache holds
    them."""
    k = jnp.asarray(rng.normal(size=(n_pages + 1, PAGE_SIZE, hkv * hd)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(n_pages + 1, PAGE_SIZE, hkv * hd)),
                    jnp.bfloat16)
    tables = np.zeros((len(lens), width), np.int32)
    perm = rng.permutation(np.arange(1, n_pages + 1))
    used = 0
    for b, n in enumerate(lens):
        npg = -(-n // PAGE_SIZE)
        tables[b, :npg] = perm[used:used + npg]
        used += npg
    return k, v, jnp.asarray(tables)


def kernel_parity(cfg):
    rng = np.random.default_rng(SEED)
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_pages = len(PROMPT_LENS) * MAX_LEN // PAGE_SIZE
    width = MAX_LEN // PAGE_SIZE
    highest = jax.default_matmul_precision("highest")

    # decode: one token per slot at each request's last decode position
    lens = [n + NEW_TOKENS - 1 for n in PROMPT_LENS]
    k, v, tables = paged_case(rng, lens, hkv, hd, n_pages, width)
    q = jnp.asarray(rng.normal(size=(len(lens), h, hd)), jnp.float32)
    pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
    got = jax.jit(pops.paged_attention)(q, k, v, tables, pos)
    with highest:
        want = jax.jit(functools.partial(pops.paged_attention,
                                         impl="view"))(q, k, v, tables, pos)
    check_close("paged decode kernel vs view", got, want, ATTN_TOL,
                ATTN_WHY)

    # prefill: the longest prompt, padded to its q-chunk boundary
    n = PROMPT_LENS[-1]
    spad = -(-n // pops.PREFILL_Q) * pops.PREFILL_Q
    k, v, tables = paged_case(rng, [n], hkv, hd, n_pages,
                              -(-spad // PAGE_SIZE))
    q = jnp.asarray(rng.normal(size=(1, spad, h, hd)), jnp.float32)
    lens_a = jnp.asarray([n], jnp.int32)
    got = jax.jit(pops.paged_prefill_attention)(q, k, v, tables, lens_a)
    with highest:
        want = jax.jit(functools.partial(pops.paged_prefill_attention,
                                         impl="view"))(q, k, v, tables,
                                                       lens_a)
    check_close("paged prefill kernel vs view", np.asarray(got)[:, :n],
                np.asarray(want)[:, :n], ATTN_TOL, ATTN_WHY)

    # quant_matmul: a decode-sized up projection and a prefill-sized down
    # projection at each width the mixed plan serves
    d, f = cfg.d_model, cfg.d_ff
    for bits in (8, 4, 2):
        for m, nn, kk in ((len(PROMPT_LENS), f, d), (spad, d, f)):
            lim = 2 ** (bits - 1)
            wq = rng.integers(-lim, lim, size=(nn, kk)).astype(np.int8)
            x = jnp.asarray(rng.normal(size=(m, kk)), jnp.float32)
            xq, sx = qref.quantize_activations(x)
            sw = jnp.asarray(np.abs(rng.normal(size=nn)) / lim, jnp.float32)
            got = qops.quant_matmul(xq, jnp.asarray(qref.pack_weights(
                wq, bits)), sw, sx, w_bits=bits)
            want = jax.jit(qref.quant_matmul_ref)(xq, jnp.asarray(wq), sw,
                                                  sx)
            check_close(f"quant_matmul w{bits} M{m} N{nn} K{kk} vs ref",
                        got, want, QMM_TOL, QMM_WHY, relative=True)


def requests(cfg):
    rng = np.random.default_rng(SEED)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=n)
                    .astype(np.int32),
                    sampling=SamplingParams(max_tokens=NEW_TOKENS))
            for i, n in enumerate(PROMPT_LENS)]


def decode_hlo_kernels(server, reqs) -> int:
    """``tpu_custom_call`` count in the server's compiled greedy decode
    step, lowered at the shapes it served these requests with (so the
    compile is found again in the persistent cache)."""
    backend = server.backend
    b = server.max_batch
    # every decode step of this batch ran at the live width of its last
    last_pos = max(len(r.prompt) for r in reqs) + NEW_TOKENS - 2
    width = server._live_width([types.SimpleNamespace(pos=last_pos)])
    compiled = server._decode_greedy.lower(
        server.params, {"tokens": jnp.zeros((b, 1), jnp.int32)},
        backend.gather(), backend.device_tables(),
        jnp.zeros((b,), jnp.int32), width).compile()
    return compiled.as_text().count("tpu_custom_call")


def serve(cfg, params, plan, reqs, label):
    server = engine.InferenceServer(
        cfg, params, plan, cache="paged", max_len=MAX_LEN,
        max_batch=len(reqs), page_size=PAGE_SIZE)
    t0 = time.perf_counter()
    out = server.serve(reqs)          # raises on NaN logits
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = server.serve(reqs)
    warm = time.perf_counter() - t0
    for r in reqs:
        toks = out.get(r.uid)
        check(toks is not None, f"{label}: request {r.uid} did not finish")
        check(toks.shape == (NEW_TOKENS,),
              f"{label}: request {r.uid} produced {toks.shape} tokens")
        check(((toks >= 0) & (toks < cfg.vocab)).all(),
              f"{label}: request {r.uid} token out of vocab")
        check(np.array_equal(toks, again[r.uid]),
              f"{label}: request {r.uid} differs between two greedy runs")
    print(f"  {label}: {len(reqs)} requests x {NEW_TOKENS} tokens, prompt "
          f"lens {[len(r.prompt) for r in reqs]}; first serve "
          f"{cold:.2f} s (with compiles), second {warm:.2f} s; decode "
          f"steps {server.stats['decode_steps']}", flush=True)
    n_calls = decode_hlo_kernels(server, reqs)
    print(f"  {label}: compiled decode step holds {n_calls} "
          f"tpu_custom_call", flush=True)
    check(n_calls > 0, f"{label}: no Pallas kernel in the decode step")


def float_logits(cfg, params, reqs):
    """Dense float prefill of the shortest prompt: finite logits of the
    expected shape."""
    prompt = jnp.asarray(reqs[0].prompt[None])
    logits, _ = jax.jit(functools.partial(lm.prefill, cfg))(
        params, {"tokens": prompt})
    logits = np.asarray(logits[..., :cfg.vocab], np.float32)
    check(logits.shape == (1, prompt.shape[1], cfg.vocab),
          f"float prefill logits shape {logits.shape}")
    check(np.isfinite(logits).all(), "float prefill logits not finite")
    print(f"  float prefill logits {logits.shape} finite, max |logit| "
          f"{float(np.max(np.abs(logits))):.3f}", flush=True)


def width_counts(bits) -> dict:
    return {int(b): int(c) for b, c in zip(*np.unique(bits,
                                                      return_counts=True))}


class LossLog(api.Hook):
    def __init__(self):
        self.values = []

    def on_step(self, phase, state, step, metrics, train_state):
        for key in ("loss", "task"):
            if key in metrics:
                self.values.append((phase.name, step, key,
                                    float(metrics[key])))


def search():
    losses = LossLog()
    comp = api.Compressor(cnn.resnet9(width=16), synthetic.CIFAR10_LIKE,
                          pw=(0, 2, 4, 8), px=(8,), batch=32, seed=SEED)
    res = comp.run([api.Warmup(steps=4),
                    api.JointSearch(steps=4, lam=10.0, cost_model="size"),
                    api.Finetune(steps=4)], hooks=[losses])
    check(len(losses.values) == 12, f"{len(losses.values)} loss values")
    bad = [v for v in losses.values if not np.isfinite(v[3])]
    check(not bad, f"non-finite search losses: {bad}")
    plan = res.plan
    check(isinstance(plan, api.CompressionPlan) and plan.channel_bits,
          "search produced no CompressionPlan")
    bits = np.concatenate([np.asarray(b) for b in plan.channel_bits.values()])
    check(np.isin(bits, plan.pw).all(), "plan bits outside pw")
    last = {p: round(v, 4) for p, _, _, v in losses.values}
    print(f"  search losses finite; last per phase {last}; plan "
          f"{len(plan.channel_bits)} groups, {bits.size} channels, "
          f"channels per width {width_counts(bits)}; acc float "
          f"{res.acc_float}, final {res.acc_final}", flush=True)


def main() -> int:
    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device {device}; compile cache {cache_dir}", flush=True)
    log = CompileLog()
    t_all = time.perf_counter()

    impl = pops.resolve_impl()
    print(f"paged_attention impl: {impl}", flush=True)
    check(impl == "kernel", f"paged attention resolves to {impl!r}")

    cfg = registry.get(ARCH)
    with phase("parity", log):
        kernel_parity(cfg)

    reqs = requests(cfg)
    with phase("serve-float", log):
        params = lm.init_params(cfg, jax.random.key(SEED))
        print(f"  {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
              f"heads {cfg.n_heads}/{cfg.n_kv_heads} x "
              f"{cfg.head_dim}, vocab {cfg.vocab}, params "
              f"{cfg.param_dtype}", flush=True)
        serve(cfg, params, None, reqs, "float")
        float_logits(cfg, params, reqs)

    with phase("serve-plan", log):
        plan = engine.synthetic_plan(cfg, params, bits=None, seed=SEED)
        mix = width_counts(np.concatenate(
            [np.asarray(b) for b in plan.channel_bits.values()]))
        print(f"  mixed plan: {len(plan.channel_bits)} groups, channels "
              f"per width {mix}", flush=True)
        check(set(mix) == {0, 2, 4, 8}, f"plan widths {sorted(mix)}")
        serve(cfg, params, plan, reqs, "plan")
    del params

    with phase("search", log):
        search()

    secs, n, hits, misses = log.snapshot()
    print(f"total wall {time.perf_counter() - t_all:.2f} s; compile "
          f"{secs:.2f} s in {n} compiles (persistent cache: {hits} hits, "
          f"{misses} misses)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
