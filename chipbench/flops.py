"""Operation counts and device peaks: the yardstick's arithmetic.

The FLOPs a training step requires follow PaLM (arXiv:2204.02311,
appendix B): ``6 N + 12 L H Q T`` per token, where ``N`` counts the
parameters outside the embedding lookup (the output head included, at
the published vocabulary, not the padded one), ``L`` layers, ``H``
query heads of size ``Q`` and ``T`` the sequence length. Operations
recomputed by rematerialisation are not counted.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def dense_decoder_params(cfg: dict) -> int:
    """Parameters of a GQA + SwiGLU decoder outside the embedding lookup."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, q = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * h * q + 2 * d * hk * q + h * q * d
    mlp = 3 * d * f
    norms = 2 * d
    head = d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (attn + mlp + norms) + d + head


def train_flops_per_token(cfg: dict, seq: int) -> float:
    n = dense_decoder_params(cfg)
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    return 6.0 * n + attn


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
