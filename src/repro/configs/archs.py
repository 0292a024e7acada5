"""The 10 assigned architectures (exact public-literature configs).

Sources per the assignment brief:
    rwkv6-7b                  [arXiv:2404.05892]
    llama4-maverick-400b-a17b [hf:meta-llama/Llama-4 family]
    qwen2-moe-a2.7b           [hf:Qwen/Qwen1.5-MoE-A2.7B]
    phi-3-vision-4.2b         [hf:microsoft/Phi-3-vision-128k-instruct]
    gemma3-4b                 [hf:google/gemma-3 family]
    mistral-large-123b        [hf:mistralai/Mistral-Large-Instruct-2407]
    granite-3-2b              [hf:ibm-granite/granite-3.0-2b-base]
    qwen3-4b                  [hf:Qwen/Qwen3 family]
    whisper-base              [arXiv:2212.04356]
    jamba-v0.1-52b            [arXiv:2403.19887]
    granite-4.0-h-micro       [hf:ibm-granite/granite-4.0-h-micro]

``reduced(cfg)`` shrinks any config to smoke-test size while preserving
its family structure (pattern, MoE, SSM, enc-dec wiring).
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import (
    AttentionConfig,
    EncDecConfig,
    Mamba2Config,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    SSMConfig,
    register,
)


@register("rwkv6-7b")
def rwkv6_7b() -> ModelConfig:
    """RWKV-6 'Finch' 7B: attention-free, data-dependent decay."""
    return ModelConfig(
        name="rwkv6-7b",
        family="ssm",
        n_layers=32,
        d_model=4096,
        d_ff=14336,
        vocab_size=65536,
        pattern=("rwkv:rwkv",),
        rwkv=RWKVConfig(head_size=64, decay_lora=64, gate_lora=64,
                        token_shift_lora=32, chunk=64),
        attention=None,
        supports_long_context=True,   # O(1) state in sequence length
    )


@register("llama4-maverick-400b-a17b")
def llama4_maverick() -> ModelConfig:
    """Llama-4 Maverick-class: 48L, alternating dense/MoE (128e top-1 +
    1 shared expert) -> ~400B total / ~17B active."""
    return ModelConfig(
        name="llama4-maverick-400b-a17b",
        family="moe",
        n_layers=48,
        d_model=5120,
        d_ff=8192,
        vocab_size=202048,
        pattern=("attn:mlp", "attn:moe"),
        attention=AttentionConfig(num_heads=40, num_kv_heads=8, head_dim=128,
                                  rope_theta=500000.0),
        moe=MoEConfig(num_experts=128, top_k=1, d_ff_expert=8192,
                      d_ff_shared=8192, capacity_factor=1.25),
        opt_state_dtype="bfloat16",   # >=100B params: bf16 m/v
    )


@register("qwen2-moe-a2.7b")
def qwen2_moe() -> ModelConfig:
    """Qwen1.5-MoE-A2.7B: 60 routed experts top-4 (padded 60->64 for EP)
    + 4 shared experts (4x1408 = 5632 merged)."""
    return ModelConfig(
        name="qwen2-moe-a2.7b",
        family="moe",
        n_layers=24,
        d_model=2048,
        d_ff=5632,
        vocab_size=151936,
        pattern=("attn:moe",),
        attention=AttentionConfig(num_heads=16, num_kv_heads=16, head_dim=128,
                                  rope_theta=1000000.0),
        moe=MoEConfig(num_experts=60, top_k=4, d_ff_expert=1408,
                      num_shared_experts=4, d_ff_shared=5632,
                      capacity_factor=1.25, padded_experts=4),
    )


@register("phi-3-vision-4.2b")
def phi3_vision() -> ModelConfig:
    """Phi-3-vision: phi3-mini backbone; CLIP frontend STUBBED —
    input_specs provide 256 precomputed patch embeddings."""
    return ModelConfig(
        name="phi-3-vision-4.2b",
        family="vlm",
        n_layers=32,
        d_model=3072,
        d_ff=8192,
        vocab_size=32064,
        pattern=("attn:mlp",),
        attention=AttentionConfig(num_heads=32, num_kv_heads=32, head_dim=96,
                                  rope_theta=10000.0),
        frontend="vision_stub",
        frontend_tokens=256,
    )


@register("gemma3-4b")
def gemma3_4b() -> ModelConfig:
    """Gemma-3 4B: 5 local (1024-window) : 1 global interleave, qk-norm,
    dual RoPE bases, tied embeddings, 262k vocab."""
    return ModelConfig(
        name="gemma3-4b",
        family="dense",
        n_layers=34,
        d_model=2560,
        d_ff=10240,
        vocab_size=262144,
        pattern=("local:mlp",) * 5 + ("attn:mlp",),
        attention=AttentionConfig(num_heads=8, num_kv_heads=4, head_dim=256,
                                  rope_theta=1000000.0, rope_theta_local=10000.0,
                                  qk_norm=True, sliding_window=1024),
        act="gelu",
        tie_embeddings=True,
        supports_long_context=True,   # ring-buffer caches on 5/6 of layers
    )


@register("mistral-large-123b")
def mistral_large() -> ModelConfig:
    return ModelConfig(
        name="mistral-large-123b",
        family="dense",
        n_layers=88,
        d_model=12288,
        d_ff=28672,
        vocab_size=32768,
        pattern=("attn:mlp",),
        attention=AttentionConfig(num_heads=96, num_kv_heads=8, head_dim=128,
                                  rope_theta=1000000.0),
        opt_state_dtype="bfloat16",   # 123B params: bf16 m/v
    )


@register("granite-3-2b")
def granite3_2b() -> ModelConfig:
    return ModelConfig(
        name="granite-3-2b",
        family="dense",
        n_layers=40,
        d_model=2048,
        d_ff=8192,
        vocab_size=49155,
        pattern=("attn:mlp",),
        attention=AttentionConfig(num_heads=32, num_kv_heads=8, head_dim=64,
                                  rope_theta=10000.0),
    )


@register("qwen3-4b")
def qwen3_4b() -> ModelConfig:
    return ModelConfig(
        name="qwen3-4b",
        family="dense",
        n_layers=36,
        d_model=2560,
        d_ff=9728,
        vocab_size=151936,
        pattern=("attn:mlp",),
        attention=AttentionConfig(num_heads=32, num_kv_heads=8, head_dim=128,
                                  rope_theta=1000000.0, qk_norm=True),
        tie_embeddings=True,
    )


@register("whisper-base")
def whisper_base() -> ModelConfig:
    """Whisper-base: 6L encoder + 6L decoder (self+cross), conv frontend
    STUBBED (frame embeddings provided). Decode shapes beyond the 448
    trained positions are nominal (see DESIGN.md)."""
    return ModelConfig(
        name="whisper-base",
        family="audio",
        n_layers=6,                    # decoder layers
        d_model=512,
        d_ff=2048,
        vocab_size=51865,
        pattern=("attnx:mlp",),
        attention=AttentionConfig(num_heads=8, num_kv_heads=8, head_dim=64),
        encdec=EncDecConfig(n_encoder_layers=6, decoder_seq_divisor=4,
                            cross_len_decode=1500),
        frontend="audio_stub",
    )


@register("jamba-v0.1-52b")
def jamba_52b() -> ModelConfig:
    """Jamba v0.1: period-8 block — 7 Mamba + 1 attention (offset 4),
    MoE (16e top-2) on every odd sublayer."""
    return ModelConfig(
        name="jamba-v0.1-52b",
        family="hybrid",
        n_layers=32,
        d_model=4096,
        d_ff=14336,
        vocab_size=65536,
        pattern=(
            "mamba:mlp", "mamba:moe", "mamba:mlp", "mamba:moe",
            "attn:mlp", "mamba:moe", "mamba:mlp", "mamba:moe",
        ),
        attention=AttentionConfig(num_heads=32, num_kv_heads=8, head_dim=128,
                                  rope_theta=10000.0),
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=256, chunk=256),
        moe=MoEConfig(num_experts=16, top_k=2, d_ff_expert=14336,
                      capacity_factor=1.25),
        supports_long_context=True,   # only 4 of 32 layers hold KV
    )


@register("granite-4.0-h-micro")
def granite4_h_micro() -> ModelConfig:
    """Granite 4.0-H Micro: period-10 block of 9 Mamba-2 mixers and one
    GQA attention mixer with no positional embedding (layer i is attention
    when i % 10 == 5), a SwiGLU MLP after every mixer, muP multipliers,
    tied embeddings."""
    return ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        n_layers=40,
        d_model=2048,
        d_ff=8192,
        vocab_size=100352,
        pattern=("mamba2:mlp",) * 5 + ("attn:mlp",) + ("mamba2:mlp",) * 4,
        attention=AttentionConfig(num_heads=32, num_kv_heads=8, head_dim=64,
                                  scale=0.015625, rope=False),
        mamba2=Mamba2Config(n_heads=64, head_dim=64, d_state=128, n_groups=1,
                            d_conv=4, expand=2, chunk=256),
        tie_embeddings=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        supports_long_context=True,   # only 4 of 40 layers hold KV
    )


# ------------------------------------------------------------------ reduced
def reduced(cfg: ModelConfig) -> ModelConfig:
    """Shrink to smoke-test size, preserving the family structure."""
    kw: dict = dict(
        n_layers=len(cfg.pattern) + min(len(cfg.remainder), 1),
        d_model=64,
        d_ff=128,
        vocab_size=512,
    )
    if cfg.attention is not None:
        kw["attention"] = dataclasses.replace(
            cfg.attention,
            num_heads=4,
            num_kv_heads=min(cfg.attention.num_kv_heads, 2),
            head_dim=16,
            q_chunk=16,
            kv_chunk=16,
            sliding_window=8 if cfg.attention.sliding_window else None,
        )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=4,
            top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=64,
            d_ff_shared=64 if cfg.moe.d_ff_shared else 0,
            padded_experts=0,
        )
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=8, dt_rank=8, chunk=16)
    if cfg.mamba2 is not None:
        # d_inner = n_heads * head_dim = expand * d_model, as published
        kw["mamba2"] = dataclasses.replace(
            cfg.mamba2, n_heads=8, head_dim=cfg.mamba2.expand * kw["d_model"] // 8,
            d_state=32, chunk=64,
        )
    if cfg.rwkv is not None:
        kw["rwkv"] = dataclasses.replace(
            cfg.rwkv, head_size=16, decay_lora=8, gate_lora=8,
            token_shift_lora=8, chunk=16,
        )
    if cfg.encdec is not None:
        kw["encdec"] = dataclasses.replace(
            cfg.encdec, n_encoder_layers=2, cross_len_decode=24
        )
    if cfg.frontend_tokens:
        kw["frontend_tokens"] = 8
    return cfg.replace(**kw)
