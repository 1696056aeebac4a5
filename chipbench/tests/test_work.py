"""Operation and byte counts against values worked out by hand."""
import dataclasses

import numpy as np
import pytest

import model
import stats
import work


@pytest.fixture(scope="module")
def cfg():
    return model.arch(model.load_config("minicpm-2b-float"))


def test_one_quant_linear_group():
    # K = 2304 inputs; 10 channels at 2 bits, 20 at 4, 30 at 8; 8 rows
    ops, byts = work.qlinear_call(2304, {2: 10, 4: 20, 8: 30}, 8)
    assert ops == 2 * 8 * 2304 * 60
    weights = 576 * 10 + 1152 * 20 + 2304 * 30   # K * bits / 8 per channel
    scales = 4 * 60
    acts = 8 * 2304 + 8 * 4                      # int8 rows + row scales
    out = 2 * 8 * 60                             # bf16 output
    assert byts == weights + scales + acts + out


def test_plan_groups_cover_every_projection(cfg):
    conf = model.load_config("minicpm-2b-mixed")
    bits = model.plan_bits(cfg, conf["plan"])
    groups = work.qlinear_groups(cfg, bits)
    assert len(groups) == 7 * cfg.n_layers
    ks = sorted({k for k, _ in groups})
    assert ks == [2304, 5760]
    # the plan draws 0/2/4/8 in the ratio 1:2:3:4 (about 4.8 bits)
    allb = np.concatenate(list(bits.values()))
    share = [np.mean(allb == b) for b in (0, 2, 4, 8)]
    assert np.allclose(share, [0.1, 0.2, 0.3, 0.4], atol=0.01)


def test_one_paged_decode_step(cfg):
    # two rows attending over 100 and 300 positions, 10 layers of
    # 36 KV heads of 64 in bf16, plus each row's query and output
    byts = work.paged_decode_bytes(cfg, [100, 300])
    kv = 400 * 2 * 36 * 64 * 2
    qo = 2 * 2 * 36 * 64 * 2
    assert byts == (kv + qo) * 10


def test_model_flops(cfg):
    lin = 10 * (4 * 2304 * 2304 + 3 * 2304 * 5760)
    assert work.linear_params(cfg, None) == lin
    att = 4 * 36 * 64 * 10
    head = 2 * 2304 * 122753
    f = work.model_flops(cfg, None, [4], [7])
    assert f == pytest.approx(4 * 2 * lin + att * 10 + head
                              + 2 * lin + att * 7 + head)


def test_pruned_channels_do_no_work(cfg):
    one = dataclasses.replace(cfg, n_layers=1)
    bits = {g: np.zeros(n, np.int64) for g, n in model.plan_groups(one).items()}
    assert work.linear_params(one, bits) == 0
    assert work.qlinear_call(2304, {}, 8)[0] == 0


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["int8_ops"] == 393e12 and pk["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_percentile():
    v = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0]
    for q in (0, 50, 95, 99, 100):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))
