"""counts.py against counts worked out by hand at the configuration's
widths, and the peaks table's refusal of an unknown device."""
import json
import os

import pytest

from chipbench import counts

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_train_flops_per_token_wt103():
    # per layer: attention projections 1024 * (2*1024 + 2*1024) = 4,194,304;
    # active MoE 4 * 2 * 1024 * 128 + router 1024 * 32 = 1,081,344;
    # xl_rel scores: 3 products * 2 * 16 * 64 * (512 + 513/2) keys
    # = 4,721,664. 18 layers + head 2 * 1024 * 8000, times 3.
    per_layer = 2 * (4_194_304 + 1_081_344) + 4_721_664
    fwd = 18 * per_layer + 16_384_000
    assert fwd == 291_297_280
    assert counts.train_flops_per_token(model("wt103-262m-moe"), 512) == \
        pytest.approx(3 * fwd, rel=1e-12)


def test_grouped_gemm_wt103_w1():
    # 16 x 513 tokens, k = 4: 32,832 routed rows of 1024 -> 128, 32 experts,
    # pre-activations saved beside the output (2 outputs per row)
    flops, nbytes = counts.grouped_gemm(32_832, 1024, 128, 32, outputs=2)
    assert flops == 2 * 32_832 * 1024 * 128 == 8_606_711_808
    assert nbytes == 2 * (32_832 * 1024 + 32 * 1024 * 128
                          + 2 * 32_832 * 128) == 92_438_528


def test_grouped_dw_and_gather():
    flops, nbytes = counts.grouped_dw(100, 1536, 512, 40)
    assert flops == 2 * 100 * 1536 * 512
    assert nbytes == 2 * 100 * (1536 + 512) + 4 * 40 * 1536 * 512
    assert counts.row_gather(64, 1536) == (0.0, 2 * 2 * 64 * 1536)


def test_ideal_time_takes_the_binding_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert counts.ideal_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert counts.ideal_s(0.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks("cpu")
