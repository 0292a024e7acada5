"""CPU rehearsals of the hybrid cell ``granite-4-h-micro.seq8k``: the
``train_hybrid`` driver drives a whole run at a tiny size and comes out
correct; the same run with the SSD broken underneath (no inter-chunk
state pass; the SSD in bfloat16) and the fp8 control come out not
correct; and the operation count matches a hand count.

Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""

from __future__ import annotations

import copy
import hashlib
import json
import os

import pytest

from chipbench import flops_hybrid, harness, traffic
from chipbench.reference import granite_hybrid as ref

import tiny  # noqa: E402  (chipbench/tests)

CELL = "granite-4-h-micro.seq8k"


def hybrid_config() -> dict:
    """The cell's configuration at the program's ``--reduce`` size: one
    whole period of 10 layers, 3 SSD chunks of 64 steps a row."""
    cfg = copy.deepcopy(harness.load_json(
        os.path.join(harness.HERE, "configs", "granite-4.0-h-micro-10L-train.json")))
    cfg.update(hidden_size=64, intermediate_size=128, shared_intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32, mamba_chunk_size=64)
    cfg["program"] = {"arch": "granite-4.0-h-micro", "reduce": True}
    cfg["train"].update(batch=2, seq=192, seq_chunk_loss=192, lr=0.01)
    return cfg


def _cell(**workload):
    return tiny.cell(CELL, hybrid_config(), **workload)


_READINGS: dict = {}


@pytest.fixture(autouse=True)
def _reference_once(monkeypatch):
    """Every run here is checked against the same float32 reference (one
    seed, one configuration, the same batches): take each reading once."""
    real = ref.readings

    def readings(cfg, seed, batches, **kw):
        key = (json.dumps(cfg, sort_keys=True), seed, repr(sorted(kw.items())),
               tuple(hashlib.sha1(b["tokens"].tobytes() + b["labels"].tobytes()).hexdigest()
                     for b in batches))
        if key not in _READINGS:
            _READINGS[key] = real(cfg, seed, batches, **kw)
        return copy.deepcopy(_READINGS[key])

    monkeypatch.setattr(ref, "readings", readings)


def _driver():
    return harness.load_module(os.path.join(harness.HERE, "drivers", "train_hybrid.py"),
                               "chipbench_driver_train_hybrid")


def test_hybrid_cell_correct():
    result = tiny.drive(_cell(), seconds=1.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(result["checks"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_program_departing_from_the_config_is_refused():
    cfg = hybrid_config()
    cfg["mamba_chunk_size"] = 8
    with pytest.raises(ValueError, match="mamba_chunk_size"):
        tiny.drive(tiny.cell(CELL, cfg), seconds=0.5)


# ------------------------------------------------------------------ broken runs
def _no_state_pass(states, chunk_decay):
    import jax.numpy as jnp

    return jnp.zeros_like(states), jnp.zeros_like(states[:, 0])


def _bf16_ssd(ssd):
    import jax.numpy as jnp

    def run(x, dt, A, B, C, chunk):
        bf = jnp.bfloat16
        y, h = ssd(x.astype(bf), dt.astype(bf), A.astype(bf), B.astype(bf), C.astype(bf), chunk)
        return y.astype(dt.dtype), h.astype(dt.dtype)

    return run


@pytest.mark.parametrize("fault", ["no_state_pass", "bf16_ssd"])
def test_broken_ssd_is_not_correct(monkeypatch, fault):
    from repro.models import mamba2

    if fault == "no_state_pass":
        monkeypatch.setattr(mamba2, "_state_pass", _no_state_pass)
    else:
        monkeypatch.setattr(mamba2, "ssd", _bf16_ssd(mamba2.ssd))
    result = tiny.drive(_cell(), seconds=0.5)
    assert not result["correct"], result["checks"]


def test_hybrid_control_is_not_correct(monkeypatch):
    """The reference in the program's place, every product in fp8."""
    drv = _driver()
    setup = drv.Job.setup

    def fp8_setup(self):
        setup(self)
        tc = self.tc
        batches = traffic.first_batches(self.run.seed, n=self.wl["check_steps"],
                                        batch=tc["batch"], seq=tc["seq"],
                                        tokens_per_shard=self.tokens_per_shard,
                                        vocab_size=self.cfg["vocab_size"])
        self.prog = ref.readings(self.cfg, self.run.seed, batches, mode="fp8")

    monkeypatch.setattr(drv.Job, "setup", fp8_setup)
    result = tiny.drive(_cell(), seconds=0.5)
    assert not result["correct"], result["checks"]


# ------------------------------------------------------------------ flops
def test_flops_hybrid_hand_count():
    """Three layers (mamba, attention, mamba) of width 8 at seq 16."""
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 3,
           "layer_types": ["mamba", "attention", "mamba", "mamba"],
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
           "vocab_size": 10, "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 3,
           "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 4}
    # in_proj 8 x (16 + 22 + 4), conv 4 x 22 + 22, 3 x 4 per head, norm 16, out_proj 16 x 8
    mamba = 8 * 42 + 88 + 22 + 12 + 16 + 128
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8
    per_layer_rest = 3 * 8 * 16 + 2 * 8
    n = 2 * mamba + attn + 3 * per_layer_rest + 8 + 8 * 10
    assert flops_hybrid.hybrid_params(cfg) == n
    ssd = 2 * 4 * 1 * 3 + 2 * 4 * 4 * 4 + 4 * 4 * 4 * 3     # C Bᵀ, (L∘CBᵀ)X, Bᵀ X and C h
    assert flops_hybrid.ssd_flops_per_token(cfg) == ssd
    assert flops_hybrid.train_flops_per_token(cfg, 16) == 6 * n + 12 * 1 * 2 * 4 * 16 + 3 * 2 * ssd


def test_flops_of_the_cell():
    cfg = harness.load_json(
        os.path.join(harness.HERE, "configs", "granite-4.0-h-micro-10L-train.json"))
    assert round(flops_hybrid.hybrid_params(cfg) / 1e6, 1) == 952.0
    per_token = flops_hybrid.train_flops_per_token(cfg, cfg["train"]["seq"])
    assert round(per_token / 1e9, 2) == 6.03
    step = per_token * cfg["train"]["batch"] * cfg["train"]["seq"]
    assert round(step / 1e13, 2) == 9.88
