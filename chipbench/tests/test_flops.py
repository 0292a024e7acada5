import math
import os

import pytest

from chipbench import flops, harness


def granite():
    return harness.load_json(os.path.join(harness.HERE, "configs", "granite-3-2b-8L-train.json"))


def test_granite_8l_params_outside_embedding():
    # 8 x (attention 10,485,760 + MLP 50,331,648 + norms 4,096) + final norm
    # 2,048 + a head of 2,048 x 49,155 = 100,669,440: the head counts
    # the published vocabulary, not the 49,408 rows the program pads it to
    assert flops.dense_decoder_params(granite()) == 587_243_520
    assert round(flops.dense_decoder_params(granite()) / 1e6, 1) == 587.2


def test_granite_8l_flops_per_token():
    cfg = granite()
    per_token = flops.train_flops_per_token(cfg, cfg["train"]["seq"])
    assert per_token == 6 * 587_243_520 + 12 * 8 * 32 * 64 * 1024
    assert math.isclose(per_token, 3.72e9, rel_tol=2e-3)


def test_v5e_peaks():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
