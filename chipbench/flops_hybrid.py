"""Operation count of a training step of the Granite 4.0-H hybrid.

Per token: ``6 N`` over the parameters outside the embedding lookup (the
tied head counted once, as the head, at the published vocabulary), plus
PaLM's ``12 L H Q T`` over the attention layers (arXiv:2204.02311,
appendix B), plus the SSD's own products in each Mamba-2 layer, three
times their forward count (forward, and the two products of the
backward pass):

- inside a chunk of ``c`` steps, ``C Bᵀ``: ``2 c G N`` a token, and the
  masked ``(L ∘ C Bᵀ) X``: ``2 c H P``;
- each chunk's state ``Bᵀ X``: ``2 H P N``;
- the state read out, ``C h``: ``2 H P N``.

Like attention's term, the chunk's square is counted whole. Operations
recomputed by rematerialisation are not counted.
"""

from __future__ import annotations


def _layers(cfg: dict) -> list[str]:
    return cfg["layer_types"][: cfg["num_hidden_layers"]]


def mamba2_params(cfg: dict) -> int:
    """One Mamba-2 mixer: in_proj, conv weight and bias, dt_bias, A_log,
    D, the gated norm and out_proj."""
    d = cfg["hidden_size"]
    h, p, n, g, k = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                     cfg["mamba_n_groups"], cfg["mamba_d_conv"])
    d_in, conv = h * p, h * p + 2 * g * n
    return d * (d_in + conv + h) + k * conv + conv + 3 * h + d_in + d_in * d


def hybrid_params(cfg: dict) -> int:
    """Parameters outside the embedding lookup: every layer (mixer, SwiGLU
    MLP, two norms), the final norm and the head."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, q = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * h * q + 2 * d * hk * q + h * q * d
    mixers = {"attention": attn, "mamba": mamba2_params(cfg)}
    per_layer = sum(mixers[kind] + 3 * d * f + 2 * d for kind in _layers(cfg))
    return per_layer + d + d * cfg["vocab_size"]


def ssd_flops_per_token(cfg: dict) -> int:
    """Forward FLOPs a token of one Mamba-2 layer's SSD products."""
    c = cfg["mamba_chunk_size"]
    h, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                  cfg["mamba_n_groups"])
    return 2 * c * g * n + 2 * c * h * p + 4 * h * p * n


def train_flops_per_token(cfg: dict, seq: int) -> float:
    kinds = _layers(cfg)
    n_attn, n_mamba = kinds.count("attention"), kinds.count("mamba")
    attn = 12 * n_attn * cfg["num_attention_heads"] * cfg["head_dim"] * seq
    ssd = 3 * n_mamba * ssd_flops_per_token(cfg)
    return 6.0 * hybrid_params(cfg) + attn + ssd
