"""granite-4.0-h-small at the smoke size on the CPU: the served paged
path against the plain float32 reference (``chipbench/reference_granite``),
the expert-parallel share of the MoE, dropless routing, the Mamba-2
decode-state kernel in interpret mode, and the new config fields'
defaults leaving the other architectures' programs as they were.

Weights are the benchmark's own seeded draw (``chipbench/model.py``), so
the state-space parameters (A, dt bias, D, conv biases) are random as on
the chip, not the training init's constants.
"""
import dataclasses
import functools
import hashlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels.ssm_decode import kernel as sk
from repro.kernels.ssm_decode import ops as sops
from repro.models import lm
from repro.nn import blocks
from repro.serve import engine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Request

CHIPBENCH = pathlib.Path(__file__).resolve().parents[1] / "chipbench"
sys.path.insert(0, str(CHIPBENCH))

import model as bench_model          # noqa: E402
import reference_granite as ref      # noqa: E402

SMOKE = registry.get("granite-4.0-h-small-smoke")
PROMPT_LENS = (20, 33)      # 33: not a multiple of the smoke chunk (32)
N_NEW = 4


@pytest.fixture(scope="module")
def smoke():
    cfg = dataclasses.replace(SMOKE, param_dtype="bfloat16")
    return cfg, bench_model.make_params(cfg, 1234)


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32)
            for n in PROMPT_LENS]


def served_logits(cfg, params, plan, prompts, n_new):
    """Serve ``prompts`` together through ``InferenceServer`` (paged
    cache, greedy, host sampling) and record the logits of every prefill
    and decode call.  Returns ``[(sequence, {position: logits})]`` per
    request: the logits that predict the token after ``position``."""
    server = engine.InferenceServer(cfg, params, plan, cache="paged",
                                    page_size=16, max_len=64,
                                    max_batch=len(prompts), pages=16,
                                    sample_on_device=False)
    rows = [{} for _ in prompts]
    prefill, decode = server._prefill_paged, server._decode

    def rec_prefill(p, tok, kv, tbl, slot, lens, width):
        logits, caches = prefill(p, tok, kv, tbl, slot, lens, width)
        rows[int(slot)][int(lens[0]) - 1] = np.asarray(
            logits.astype(jnp.float32))[0, -1, :cfg.vocab]
        return logits, caches

    def rec_decode(p, t, c, tbl, pos, width):
        logits, caches = decode(p, t, c, tbl, pos, width)
        lg = np.asarray(logits.astype(jnp.float32))[:, -1, :cfg.vocab]
        for slot, at in enumerate(np.asarray(pos)):
            rows[slot][int(at)] = lg[slot]
        return logits, caches

    server._prefill_paged, server._decode = rec_prefill, rec_decode
    out = server.serve([Request(uid=i, prompt=p,
                                sampling=SamplingParams(max_tokens=n_new))
                        for i, p in enumerate(prompts)])
    # admission is FIFO into free slots: request i decodes in slot i
    return [(np.concatenate([p, out[i][:-1]]), rows[i])
            for i, p in enumerate(prompts)]


def reference_logits(cfg, params, bits, seq, lowp=False):
    sb = None if bits is None else jax.tree.map(
        jnp.asarray, ref.stack_bits(bits, cfg))
    h = ref.hidden(params, sb, jnp.asarray(seq), dims=ref.dims(cfg),
                   lowp=lowp)
    return np.asarray(ref.logits(params, h, vocab=cfg.vocab, lowp=lowp))


# --- the layer pattern and the expert share ------------------------------

def test_pattern_is_one_attention_layer_in_ten_with_moe_everywhere():
    cfg = registry.get("granite-4.0-h-small")
    pat = lm.block_pattern(cfg)
    assert [s.mixer for s in pat] == ["mamba"] * 5 + ["attn"] + \
        ["mamba"] * 4
    assert all(s.ffn == "moe" for s in pat)
    assert lm.n_superblocks(cfg) == 4
    assert (cfg.d_inner, cfg.ssm_heads) == (8192, 128)
    # the smoke sibling keeps the whole period
    assert [s.mixer for s in lm.block_pattern(SMOKE)] == \
        [s.mixer for s in pat] and SMOKE.n_layers == 10


def test_published_size_and_held_share():
    cfg = registry.get("granite-4.0-h-small")
    n = sum(int(np.prod(x.shape))
            for x in jax.tree.leaves(lm.abstract_params(cfg)))
    assert 31e9 < n < 33e9, n / 1e9          # "32B total"
    held = dataclasses.replace(cfg, n_experts_held=9)
    tree = lm.abstract_params(held)["blocks"]["l0"]["ffn"]
    assert tree["router"]["w"].shape == (4, 4096, 72)
    assert tree["w_gate"]["w"].shape == (4, 9, 4096, 768)
    assert "lm_head" not in lm.abstract_params(cfg)     # tied


# --- the served path against the plain reference -------------------------

# The tolerance is on the mean, over the served positions, of each
# position's rms logit error relative to the spread (std) of the
# reference logits there.  bf16 weights, activations and KV through ten
# layers, with top-2-of-4 routing that a rounding flips at some
# positions, read 0.04-0.10 (seeds 1234 and 99, float and plan); the
# reference one precision lower (float8 operands, the oracle's control)
# reads 0.35-0.80.  A single position can read 0.27 served and 0.16
# under the control, so no per-position bound separates them.
LOGIT_TOL = 0.2


@pytest.mark.parametrize("planned", [False, True], ids=["float", "plan"])
def test_served_logits_match_the_reference(smoke, planned):
    cfg, params = smoke
    plan = engine.synthetic_plan(cfg, params, seed=3) if planned else None
    bits = None if plan is None else plan.channel_bits
    dev, ctl = [], []
    for seq, rows in served_logits(cfg, params, plan, _prompts(cfg),
                                   N_NEW):
        want = reference_logits(cfg, params, bits, seq)
        low = reference_logits(cfg, params, bits, seq, lowp=True)
        # the prefill's last prompt token, then every decode step
        assert sorted(rows) == list(range(len(seq) - N_NEW, len(seq)))
        for at, got in rows.items():
            spread = np.std(want[at])
            dev.append(np.sqrt(np.mean((got - want[at]) ** 2)) / spread)
            ctl.append(np.sqrt(np.mean((low[at] - want[at]) ** 2)) / spread)
    assert np.mean(dev) < LOGIT_TOL, dev
    assert np.mean(ctl) > LOGIT_TOL, ctl


# --- MoE: the expert-parallel share and dropless routing -----------------

@pytest.fixture(scope="module")
def moe_case():
    """One smoke MoE layer with 16 experts, top 4, in float32."""
    cfg = dataclasses.replace(SMOKE, n_experts=16, experts_per_token=4,
                              param_dtype="float32")
    p = jax.tree.map(lambda a: a[0], lm.init_params(
        cfg, jax.random.key(7))["blocks"]["l0"]["ffn"])
    x = jax.random.normal(jax.random.key(2), (3, 5, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_expert_shares_add_up_to_the_uncut_layer(moe_case):
    """Eight shards of two experts each: their parts of the routed
    result, with the shared expert counted once, are the whole layer."""
    cfg, p, x = moe_case
    whole = blocks.moe_layer(p, x, cfg)
    xx = x.reshape(-1, cfg.d_model)
    parts = []
    for shard in range(8):
        sl = slice(2 * shard, 2 * shard + 2)
        parts.append(blocks._moe_local(
            xx, p["router"]["w"], p["w_gate"]["w"][sl], p["w_up"]["w"][sl],
            p["w_down"]["w"][sl], top_k=4, capacity=None,
            e_offset=2 * shard, renorm=True))
    shared = blocks.ffn_swiglu(p["shared"], x)
    got = sum(parts).reshape(x.shape) + shared
    # f32 throughout; the sum of parts reassociates the expert sum
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # the first shard as the configuration holds it: its banks alone
    held = dataclasses.replace(cfg, n_experts_held=2)
    ph = dict(p, **{k: {"w": p[k]["w"][:2]}
                    for k in ("w_gate", "w_up", "w_down")})
    np.testing.assert_allclose(
        np.asarray(blocks.moe_layer(ph, x, held)),
        np.asarray(parts[0].reshape(x.shape) + shared), rtol=1e-6,
        atol=1e-6)


def test_routing_renormalises_the_top_k(moe_case):
    cfg, p, x = moe_case
    xx = x.reshape(-1, cfg.d_model)
    one = {k: {"w": jnp.ones_like(p[k]["w"][:1])}
           for k in ("w_gate", "w_up", "w_down")}
    # expert e holds identical all-ones banks; each row's gates sum to 1
    # over its top 4, so summing every expert's part gives the same
    # output as a single expert with gate 1
    parts = sum(blocks._moe_local(
        xx, p["router"]["w"], one["w_gate"]["w"], one["w_up"]["w"],
        one["w_down"]["w"], top_k=4, capacity=None, e_offset=e,
        renorm=True) for e in range(cfg.n_experts))
    single = blocks._moe_local(
        xx, jnp.zeros_like(p["router"]["w"][:, :1]), one["w_gate"]["w"],
        one["w_up"]["w"], one["w_down"]["w"], top_k=1, capacity=None,
        e_offset=0, renorm=True)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(single),
                               rtol=1e-5)


def test_moe_batched_equals_solo(moe_case):
    """Dropless: a row's result does not depend on the other rows."""
    cfg, p, x = moe_case
    cfg = dataclasses.replace(cfg, n_experts_held=4)
    ph = dict(p, **{k: {"w": p[k]["w"][:4]}
                    for k in ("w_gate", "w_up", "w_down")})
    xb = jnp.tile(x.reshape(1, -1, cfg.d_model), (8, 1, 1))  # 8 x 15 rows
    batched = np.asarray(blocks.moe_layer(ph, xb.reshape(-1, 1, cfg.d_model),
                                          cfg))
    for r in range(0, 120, 17):
        solo = np.asarray(blocks.moe_layer(
            ph, xb.reshape(-1, 1, cfg.d_model)[r:r + 1], cfg))
        # f32: a one-row and a 120-row matmul sum in different orders
        # on the CPU (a few 1e-7); a dropped row would differ by O(1)
        np.testing.assert_allclose(batched[r:r + 1], solo, rtol=0,
                                   atol=1e-5)
    # the capacity-bound layer drops rows at this batch, so it differs
    capped = dataclasses.replace(cfg, capacity_factor=1.0)
    assert not np.allclose(
        np.asarray(blocks.moe_layer(ph, xb.reshape(-1, 1, cfg.d_model),
                                    capped)), batched)


_SHARDED_MOE = """
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import registry
from repro.distributed import sharding
from repro.models import lm
from repro.nn import blocks
cfg = dataclasses.replace(registry.get("granite-4.0-h-small-smoke"),
                          n_experts=8, experts_per_token=3)
p = jax.tree.map(lambda a: a[0], lm.init_params(
    cfg, jax.random.key(0))["blocks"]["l0"]["ffn"])
x = jax.random.normal(jax.random.key(1), (4, 3, cfg.d_model))
local = blocks.moe_layer(p, x, cfg)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
with sharding.use_mesh(mesh, {"batch": "data", "experts": "model"}):
    sharded = jax.jit(lambda p, x: blocks.moe_layer(p, x, cfg))(p, x)
print(float(jnp.max(jnp.abs(sharded - local))))
"""


def test_expert_parallel_layer_matches_the_local_layer():
    """The shard_map path over 2 x 2 CPU devices (each model shard holds
    4 of 8 experts, numbered from its axis index) against the one-device
    layer, dropless with the renormalised top 3."""
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(CHIPBENCH.parent / "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED_MOE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    # f32; the psum over the model axis reassociates the expert sum
    assert float(out.stdout.split()[-1]) < 1e-5


# --- the Mamba-2 decode-state kernel ---------------------------------------

@pytest.mark.parametrize("shape", [(3, 8, 16, 16), (2, 64, 64, 128),
                                   (2, 4, 128, 128)],
                         ids=["smoke", "granite-heads", "jamba-heads"])
def test_ssm_decode_kernel_matches_ref(shape):
    b, h, p, n = shape
    ks = jax.random.split(jax.random.key(0), 7)
    state = jax.random.normal(ks[0], shape, jnp.float32)
    x = jax.random.normal(ks[1], (b, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, h), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[3], (h,), jnp.float32))
    bb = jax.random.normal(ks[4], (b, n), jnp.float32)
    cc = jax.random.normal(ks[5], (b, n), jnp.float32)
    d = jax.random.normal(ks[6], (h,), jnp.float32)
    y, s_new = sk.ssm_decode_step_fwd(state, x, dt, a, bb, cc, d,
                                      bh=min(32, h), interpret=True)
    y_ref, s_ref = sops.ssm_decode_ref(state, x, dt, a, bb, cc, d)
    # f32 on both sides; the kernel sums y over d_state in another order
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(s_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


def test_decode_branch_runs_the_kernel_where_asked(smoke, monkeypatch):
    """The Mamba layer's decode step gives the same tokens with the
    kernel (interpret mode) as with the jnp reference."""
    cfg, params = smoke
    prompts = _prompts(cfg, seed=5)
    want = served_logits(cfg, params, None, prompts, 3)
    monkeypatch.setattr(sops, "ssm_decode_step", functools.partial(
        sops.ssm_decode_step, use_kernel=True))
    monkeypatch.setattr(sk, "DEFAULT_BH", 4)
    got = served_logits(cfg, params, None, prompts, 3)
    for (s1, r1), (s2, r2) in zip(want, got):
        np.testing.assert_array_equal(s1, s2)
        for at in r1:
            # the kernel's f32 state equals the einsums' to 1e-6; bf16
            # logits then differ by at most a rounding or two
            np.testing.assert_allclose(r2[at], r1[at], rtol=0, atol=1e-3)


# --- the new fields' defaults change no other architecture -----------------

def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a, np.float32))
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# smoke parameter trees (init_params, key 0) and the served logits of
# three greedy tokens for two prompts, as the code read before the
# granite fields existed
PINNED = {
    "minicpm-2b": (
        "d09e4c5817131c2ea20e660aa5a6df9393eca9a712f9ff39e783a8516e106f47",
        "37c89701a4089e187830622afd53f70792b259e7883fc9d4e2691da905697063"),
    "jamba-1.5-large-398b": (
        "03108b66d466ea33388d5565d308e7136575dbe6dd39e7bea8ae3901e001aa51",
        "2d5f899add32f6f3f8e22445a7f2c7ee186322bc7aebbb510e77f971a83f128f"),
}


@pytest.mark.parametrize("arch", sorted(PINNED))
def test_defaults_leave_other_architectures_unchanged(arch):
    cfg = registry.get(arch + "-smoke")
    params = lm.init_params(cfg, jax.random.key(0))
    assert _digest(jax.tree.leaves(params)) == PINNED[arch][0]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (20, 33)]
    served = served_logits(cfg, params, None, prompts, 3)
    assert _digest([r[at] for _, r in served for at in sorted(r)]) == \
        PINNED[arch][1]
