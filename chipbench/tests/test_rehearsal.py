"""A whole run of each configuration's path at a tiny size on the CPU.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run -- seeded weights and plan, the server, warm-up, ramp,
window, drain and the check against the configuration's own plain
reference -- runs as on the chip, with the smoke-sized sibling of the
configuration's architecture (``<arch>-smoke``).  Then the timed path is
broken underneath and the check has to fail.  Every file in
``configs/`` is rehearsed, so a new configuration needs no edit here.
"""
import copy
import time

import pytest

import harness
import model

CONFIGS = sorted(p.stem for p in (harness.HERE / "configs").glob("*.json"))

SMOKE_MIX = {"loop": "closed", "clients": 4, "max_batch": 4, "max_len": 64,
             "page_size": 16, "pages": 16, "prompt_lengths": [16, 32],
             "prompt_weights": [1, 1], "output_min": 4, "output_max": 8,
             "block": 4, "greedy": True, "check_requests": 3}
LIMIT = 0.02     # smoke size: the served tokens' widest gap, see below


def smoke_spec(config: str) -> dict:
    conf = copy.deepcopy(model.load_config(config))
    conf["arch"] += "-smoke"
    conf["overrides"] = {"param_dtype": "bfloat16"}
    return {"cell": {"name": f"smoke.{config}", "config": config,
                     "traffic": "smoke", "chips": 1},
            "conf": conf, **harness.config_modules(conf),
            "mix": dict(SMOKE_MIX),
            "limits": {"logit_gap": LIMIT},
            "end_to_end": [{"name": n, "unit": u} for n, u in (
                ("output_tok_s", "tokens/s"), ("ttft_p95_ms", "ms"),
                ("itl_p95_ms", "ms"), ("setup_s", "s"))],
            "per_layer": []}


def run(config, seed=3, seconds=1.0):
    lines = []
    out = harness.run(smoke_spec(config), seed, seconds, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      log=lines.append)
    return out, lines


@pytest.mark.parametrize("config", CONFIGS)
def test_run_is_correct(config):
    out, lines = run(config)
    assert out["correct"], (out["checks"], lines)
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    for name in ("output_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"):
        assert out["metrics"][name]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert any(l.startswith("compiles in the window") for l in lines)


def _altered_token(monkeypatch):
    """A decoded token changed where the server produces it."""
    from repro.serve import engine
    orig = engine.InferenceServer._decode_active

    def bad(self, active):
        toks = orig(self, active)
        return {s: (t + 1) % self.cfg.vocab for s, t in toks.items()}

    monkeypatch.setattr(engine.InferenceServer, "_decode_active", bad)


def _state_unchanged(monkeypatch):
    """A decode step that hands back the cache it was given: the token's
    K and V are never written."""
    from repro.models import lm
    orig = lm.decode_step

    def bad(cfg, params, token_batch, caches, pos, tables=None):
        logits, _ = orig(cfg, params, token_batch, caches, pos,
                         tables=tables)
        return logits, caches

    monkeypatch.setattr(lm, "decode_step", bad)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
@pytest.mark.parametrize("config", CONFIGS)
def test_broken_path_is_not_correct(monkeypatch, config, fault):
    fault(monkeypatch)
    out, _ = run(config, seed=5)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("config", CONFIGS)
def test_control_is_not_correct(config):
    out = harness.run(smoke_spec(config), 7, 1.0, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      control=True, log=lambda _: None)
    c = out["checks"]
    assert c["control_gap"]["value"] > LIMIT >= c["logit_gap"]["value"]
