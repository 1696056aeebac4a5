"""The dense configurations' reference module and plan, pinned: the plan
bits, their layer stacking and the reference's static sizes are what
they were before a configuration could name its own reference module."""
import hashlib

import numpy as np
import pytest

import harness
import model

CONFIGS = ("minicpm-2b-float", "minicpm-2b-mixed")
# sha256 of the sorted arrays (name, dtype, shape, bytes), as the
# benchmark drew and stacked them when the dense layout was fixed in code
PLAN_BITS = "f5d9485565d19bd196c956c654d716f7758f60e477a06d0cb03d58db2e8b64c6"
STACKED = "53d0ccfee71417f9064cca2fa736c0777a93ee7b0b26f099e59798d937bd60d7"


def digest(arrays: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.ascontiguousarray(arrays[k])
        h.update(f"{k}|{a.dtype.str}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def mixed_plan():
    return model.load_config("minicpm-2b-mixed")["plan"]


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_modules_are_the_default(config):
    mods = harness.config_modules(model.load_config(config))
    assert mods["reference"].__file__ == str(harness.HERE / "reference.py")
    assert mods["work"].__file__ == str(harness.HERE / "work.py")


@pytest.mark.parametrize("config", CONFIGS)
def test_dims(config):
    conf = model.load_config(config)
    cfg = model.arch(conf)
    ref = harness.config_modules(conf)["reference"]
    assert ref.dims(cfg) == (36, 64, 1e-6, 10000.0)
    assert ref.dims(cfg) == (cfg.n_heads, cfg.head_dim, cfg.norm_eps,
                             cfg.rope_theta)


@pytest.mark.parametrize("config", CONFIGS)
def test_plan_and_stacking_are_pinned(config, mixed_plan):
    # the float configuration states no plan: draw the mixed one's over it
    conf = model.load_config(config)
    cfg = model.arch(conf)
    ref = harness.config_modules(conf)["reference"]
    bits = model.plan_bits(cfg, mixed_plan, ref)
    assert digest(bits) == PLAN_BITS
    assert digest(model.plan_bits(cfg, mixed_plan)) == PLAN_BITS
    stacked = ref.stack_bits(bits, cfg)
    assert digest(stacked) == STACKED
    for proj, arr in stacked.items():
        assert arr.shape[0] == cfg.n_layers and arr.dtype == np.int32
        for j in range(cfg.n_layers):
            np.testing.assert_array_equal(arr[j],
                                          bits[f"blocks.l0.{proj}.sb{j}"])


def test_stacking_refuses_deeper_super_blocks():
    conf = model.load_config("minicpm-2b-mixed")
    cfg = model.arch(conf)
    ref = harness.config_modules(conf)["reference"]
    with pytest.raises(ValueError, match="one layer per super-block"):
        ref.stack_bits({"blocks.l1.mixer.wq.sb0": np.zeros(4)}, cfg)


def test_plan_groups_of_the_reference_are_drawn(mixed_plan):
    class Ref:
        @staticmethod
        def plan_groups(cfg):
            return {"b.experts": 5, "a.experts": 3}

    cfg = model.arch(model.load_config("minicpm-2b-mixed"))
    bits = model.plan_bits(cfg, mixed_plan, Ref)
    assert list(bits) == ["a.experts", "b.experts"]
    assert [len(v) for v in bits.values()] == [3, 5]


def test_unknown_module_is_refused():
    conf = dict(model.load_config("minicpm-2b-mixed"),
                reference="no_such_reference")
    with pytest.raises(ValueError, match="no_such_reference"):
        harness.config_modules(conf)
