"""granite-4.0-h-small-mixed: its configuration, work counts and the
``ssm_decode_roofline`` reader, against values worked out by hand."""
import importlib.util
import pathlib

import numpy as np
import pytest

import harness
import model
import work
import work_granite as wg
from trace_reduce import Context, Event, reduce_events

CONFIG = "granite-4.0-h-small-mixed"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


@pytest.fixture(scope="module")
def cfg():
    return model.arch(model.load_config(CONFIG))


def reader(name):
    path = pathlib.Path(__file__).parents[1] / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_configuration_is_the_catalog_entry_cut_to_one_stage(cfg):
    conf = model.load_config(CONFIG)
    src = conf["source_config"]
    for key, value in src.items():
        if key in conf["reduced"]:
            assert [src[key], conf[key]] == conf["reduced"][key]
        else:
            assert conf[key] == value, key
    assert conf["reduced"] == {"num_hidden_layers": [40, 10],
                               "num_local_experts": [72, 9],
                               "tie_word_embeddings": [True, False]}
    assert not cfg.tie_embeddings
    assert (cfg.n_layers, cfg.n_experts, cfg.experts_held,
            cfg.experts_per_token) == (10, 72, 9, 10)
    assert (cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_state,
            cfg.expert_d_ff, cfg.d_ff, cfg.vocab) == (
        4096, 8192, 128, 128, 768, 1536, 100352)
    assert cfg.attn_scale == src["attention_multiplier"]
    assert cfg.embed_scale == src["embedding_multiplier"]
    assert cfg.residual_scale == src["residual_multiplier"]
    assert cfg.logits_scaling == src["logits_scaling"]
    mods = harness.config_modules(conf)
    assert pathlib.Path(mods["reference"].__file__).name == \
        "reference_granite.py"
    assert pathlib.Path(mods["work"].__file__).name == "work_granite.py"


def test_plan_covers_the_two_dimensional_projections(cfg):
    bits = model.plan_bits(cfg, model.load_config(CONFIG)["plan"])
    # 9 Mamba layers x 6, one attention layer x 4, 10 shared experts x 3
    assert len(bits) == 88
    groups = wg.qlinear_groups(cfg, bits)
    ks = sorted({k for k, _ in groups})
    assert ks == [1536, 4096, 8192]        # shared down, hidden, d_inner


def test_ssm_decode_bytes(cfg):
    # per row and Mamba layer: the f32 state (128 heads x 64 x 128) read
    # and written, x and y (128 x 64), B and C (128), dt (128)
    per_row = 4 * (2 * 128 * 64 * 128 + 2 * 128 * 64 + 2 * 128 + 128)
    assert wg.ssm_decode_bytes(cfg, 64) == 64 * per_row * 9
    # the state alone, 9 layers at 64 slots: 4.83 GB of the 4.87
    assert 9 * 64 * 8 * 128 * 64 * 128 == 4_831_838_208
    assert 4.87e9 < wg.ssm_decode_bytes(cfg, 64) < 4.88e9


def test_one_attention_layer_in_ten(cfg):
    byts = wg.paged_decode_bytes(cfg, [100, 300])
    kv = 400 * 2 * 8 * 128 * 2            # 8 KV heads of 128 in bf16
    qo = 2 * 2 * 32 * 128 * 2
    assert byts == kv + qo


def test_model_flops_count_the_held_expert_share(cfg):
    d, f = 4096, 768
    planned = 9 * (4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096) \
        + (4096 * (4096 + 2 * 1024) + 4096 * 4096) + 10 * 3 * 4096 * 1536
    assert wg.linear_params(cfg, None) == planned
    per_tok = (2 * planned
               + 10 * 2 * (10 * 9 / 72 * 3 * d * f + d * 72)
               + 9 * (5 * 128 * 64 * 128 + 2 * 4 * (8192 + 2 * 128)))
    att = 4 * 32 * 128 * 1
    head = 2 * d * 100352
    assert wg.model_flops(cfg, None, [3], [7]) == pytest.approx(
        3 * per_tok + att * 6 + head + per_tok + att * 7 + head)
    bits = {g: np.zeros(n, np.int64)
            for g, n in model.plan_groups(cfg).items()}
    assert wg.linear_params(cfg, bits) == 0


def test_qlinear_roofline_matches_the_dense_counts(cfg):
    # one planned projection's call is counted as work.py counts it
    bits = {"blocks.l5.mixer.wq.sb0": np.array([8] * 30 + [2] * 10)}
    pk = work.peaks("TPU v5 lite")
    ops, byts = work.qlinear_call(4096, {8: 30, 2: 10}, 64)
    assert wg.qlinear_roofline_s(cfg, bits, [64], pk) == pytest.approx(
        max(ops / pk["int8_ops"], byts / pk["hbm_bytes_s"]))


def _trace(kernel_name):
    def ev(plane, line, name, start, dur):
        return Event(plane, line, name, float(start), float(dur), name)
    return [ev(HOST, "python", "chipbench.step", 0, 10_000),
            ev(DEV, "XLA Modules", "jit_decode_greedy(3)", 0, 9_000),
            ev(DEV, "XLA Ops",
               f"%{kernel_name}.4 = (f32[64,128,64]{{2,1,0}}, "
               "f32[64,128,64,128]{3,2,1,0}) custom-call(f32[64,128,64,"
               "128]{3,2,1,0} %bitcast.2, f32[64,128,64]{2,1,0} %f.1)",
               0, 4_000),
            ev(DEV, "XLA Ops", "%fusion.7 = f32[64,128,64]{2,1,0} "
               "fusion(f32[64,128,64]{2,1,0} %ssm_decode_step.4)", 4_000,
               1_000)]


class _Step:
    def __init__(self, rows):
        self.decode_ctx = [100] * rows
        self.prefill = []


def test_ssm_decode_roofline_reads_the_kernel_by_name(cfg):
    pk = work.peaks("TPU v5 lite")
    ctx = Context(trace=reduce_events(_trace("ssm_decode_step"),
                                      "chipbench.step"),
                  steps=[_Step(64), _Step(0), _Step(60)], cfg=cfg,
                  bits=None, peaks=pk, work=wg)
    # 4,000 ns of kernel time for two decode steps of 64 and 60 rows;
    # the fusion that reads the kernel's output is not the kernel
    want = 100 * (wg.ssm_decode_bytes(cfg, 64) + wg.ssm_decode_bytes(
        cfg, 60)) / pk["hbm_bytes_s"] / 4e-6
    assert reader("ssm_decode_roofline").read(ctx) == pytest.approx(want)
    # a program without the kernel, or a work module without the count
    ctx.trace = reduce_events(_trace("custom-call"), "chipbench.step")
    assert reader("ssm_decode_roofline").read(ctx) is None
    ctx.work = work
    assert reader("ssm_decode_roofline").read(ctx) is None


def test_the_kernel_is_not_read_as_another_kernel():
    events = _trace("ssm_decode_step")
    for name in ("qlinear_roofline", "paged_decode_roofline"):
        r = reduce_events(events, "chipbench.step")
        assert r.kernel(reader(name).KERNEL) == (0, 0.0), name
