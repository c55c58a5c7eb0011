"""counts_mla.py against the program's own parameter count of the same cut
and against counts worked out by hand."""
import dataclasses
import json
import os

import pytest

from chipbench import counts_mla

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(HERE, "configs",
                           "moonlight-16b-a3b-ep8.json")) as f:
        return json.load(f)["model"]


def test_mla_params_by_hand(model):
    # W_q 2048 x 16*192, W_kv_a 2048 x (512 + 64), W_kv_b 512 x 16*(128+128),
    # W_o 16*128 x 2048
    assert counts_mla.mla_params(model) == (
        6_291_456 + 1_179_648 + 2_097_152 + 4_194_304)


def test_active_params_match_the_program(model):
    from repro.configs import get_config
    cfg = get_config("moonlight-16b-a3b").override(
        n_layers=model["n_layers"], vocab_size=model["vocab_size"])
    cfg = cfg.with_ffn(dataclasses.replace(cfg.ffn,
                                           n_experts=model["n_experts"]))
    # the program's count holds the latent norm's 512 scales in each layer,
    # which multiply no matrix
    assert counts_mla.active_params(model) == pytest.approx(
        cfg.param_counts()["active"] - 6 * 512, abs=1)


def test_train_flops_per_token(model):
    # 6 N_active, plus 3 x 6 layers x 2 x 16 heads x (192 + 128) x 4097 keys
    attn = 3 * 6 * 2 * 16 * 320 * 4097
    assert counts_mla.train_flops_per_token(model, 8192) == pytest.approx(
        6 * counts_mla.active_params(model) + attn, rel=1e-12)
