"""Tiny cells for CPU rehearsals: the benchmark's own configurations cut to
a size a test run holds, and a helper that drives a whole run."""

from __future__ import annotations

import copy
import json
import os
import time

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def train_config() -> dict:
    cfg = harness.load_json(os.path.join(harness.HERE, "configs", "granite-3-2b-8L-train.json"))
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
               embedding_multiplier=8.0, attention_multiplier=0.25)
    cfg["program"] = {"arch": "granite-3-2b", "reduce": True}
    cfg["train"].update(batch=4, seq=32, seq_chunk_loss=32)
    return cfg


def incr_config() -> dict:
    cfg = copy.deepcopy(harness.load_json(
        os.path.join(harness.HERE, "configs", "incr-bigbrain-617MiB.json")))
    cfg.update(block_elems=1 << 14, block_bytes=4 << 14)
    return cfg


def cell(name: str, config: dict, **workload) -> harness.Cell:
    cell = harness.Cell.load(BENCH, name)
    cell.config = config
    cell.workload = dict(cell.workload, **workload)
    return cell


def drive(cell: harness.Cell, seed: int = 12345, seconds: float = 0.5) -> dict:
    import jax

    result = harness.execute(cell, seed=seed, seconds=seconds, trace=False,
                             devices=jax.devices(), t_start=time.monotonic(),
                             log=lambda *_: None)
    json.dumps(result)
    return result
