"""GQA attention: a fused causal kernel on TPU, flash-style chunked
online-softmax in pure XLA elsewhere, sliding windows, KV caches (full +
ring-buffer for local layers), decode paths.

Training self-attention (``attn_apply``) runs JAX's Pallas splash kernel
(``fused_causal_attention``) on TPU when the call is plain causal, unsharded
and of a tileable shape; the score tile stays in VMEM, blocks above the
diagonal are skipped, and its custom VJP is the backward pass. Every other
call (the CPU, windows, softcaps, cross-attention, prefill, a sharded mesh)
runs ``chunked_attention``, which also serves as the oracle of the Pallas
kernels at small sizes. Scores/softmax statistics accumulate in fp32; the
chunked P·V matmul runs in the compute dtype for the MXU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.configs.base import AttentionConfig
from repro.distributed.sharding import DP, FSDP, TP, shard_hint, unsharded
from repro.models.layers import (
    Layout,
    apply_rope,
    dense_init,
    norm_init,
    qk_head_norm,
)

NEG_INF = -1e30


# --------------------------------------------------------------- core math
def _chunk_attend(q, k, v, qpos, kpos, *, causal, window, softcap, compute_dtype):
    """One (q-chunk, kv-chunk) tile: returns fp32 (scores_exp, m, l, pv).

    q: [B, Hk, G, Lq, Dh]   k/v: [B, Hk, Lk, Dh]
    qpos: [Lq], kpos: [Lk]  absolute positions for masking.
    """
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", q, k, preferred_element_type=jnp.float32
    )
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.ones((q.shape[3], k.shape[2]), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s


def chunked_attention(
    q: jax.Array,            # [B, Sq, H, Dh]
    k: jax.Array,            # [B, Sk, Hk, Dh]
    v: jax.Array,            # [B, Sk, Hk, Dh]
    *,
    causal: bool = True,
    window: int | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    softcap: float | None = None,
    q_offset: int = 0,       # absolute position of q[0] (prefill continuation)
    scale: float | None = None,   # None: 1/sqrt(Dh)
) -> jax.Array:
    """Flash-style attention with O(S·chunk) live memory."""
    B, Sq, H, Dh = q.shape
    _, Sk, Hk, _ = k.shape
    G = H // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    cdt = q.dtype

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    pad_q = nq * q_chunk - Sq
    pad_k = nk * kv_chunk - Sk

    qr = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kr = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vr = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    # [B, Hk, G, S, Dh] / [B, Hk, S, Dh]
    qr = (qr.reshape(B, nq * q_chunk, Hk, G, Dh) * scale).transpose(0, 2, 3, 1, 4)
    kr = kr.transpose(0, 2, 1, 3)
    vr = vr.transpose(0, 2, 1, 3)

    kpos_all = jnp.arange(nk * kv_chunk)
    kvalid = kpos_all < Sk

    def q_body(_, qi):
        qblk = jax.lax.dynamic_slice_in_dim(qr, qi * q_chunk, q_chunk, axis=3)
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def kv_body(carry, ki):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(kr, ki * kv_chunk, kv_chunk, axis=2)
            vblk = jax.lax.dynamic_slice_in_dim(vr, ki * kv_chunk, kv_chunk, axis=2)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = _chunk_attend(
                qblk, kblk, vblk, qpos, kpos,
                causal=causal, window=window, softcap=softcap, compute_dtype=cdt,
            )
            s = jnp.where(
                jax.lax.dynamic_slice_in_dim(kvalid, ki * kv_chunk, kv_chunk)[
                    None, None, None, None, :
                ],
                s,
                NEG_INF,
            )
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhgqk,bhkd->bhgqd",
                p.astype(cdt),
                vblk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hk, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hk, G, q_chunk, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(cdt)

    _, blocks = jax.lax.scan(q_body, None, jnp.arange(nq))
    # blocks: [nq, B, Hk, G, q_chunk, Dh] -> [B, S, H, Dh]
    out = blocks.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * q_chunk, H, Dh)
    return out[:, :Sq]


def _splash_block(S: int) -> int | None:
    """The largest tile of 512, 256 or 128 rows that divides S."""
    return next((b for b in (512, 256, 128) if S % b == 0), None)


def fused_causal_attention(
    q: jax.Array,            # [B, S, H, Dh]
    k: jax.Array,            # [B, S, Hk, Dh]
    v: jax.Array,            # [B, S, Hk, Dh]
    *,
    scale: float | None = None,   # None: 1/sqrt(Dh)
    interpret: bool = False,
) -> jax.Array:
    """Causal self-attention as JAX's Pallas splash kernel (TPU).

    One MQA kernel of G = H/Hk query heads per KV head, vmapped over batch
    and KV heads, so GQA needs no repeat of K/V. Blocks above the diagonal
    are skipped; the custom VJP recomputes the tiles in the backward pass,
    so no score tensor reaches HBM. ``interpret=True`` runs the Pallas
    interpreter (CPU validation).
    """
    B, S, H, Dh = q.shape
    Hk = k.shape[2]
    G = H // Hk
    bs = _splash_block(S)
    blocks = splash.BlockSizes(
        block_q=bs, block_kv=bs, block_kv_compute=bs,
        block_q_dkv=bs, block_kv_dkv=bs, block_kv_dkv_compute=bs,
        block_q_dq=bs, block_kv_dq=bs,
    )
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * G)
    kernel = splash.make_splash_mqa_single_device(
        mask, block_sizes=blocks, interpret=interpret
    )
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    # [B, Hk, G, S, Dh] / [B, Hk, S, Dh]
    qr = (q.reshape(B, S, Hk, G, Dh) * scale).transpose(0, 2, 3, 1, 4)
    kr = k.transpose(0, 2, 1, 3)
    vr = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(qr, kr, vr)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh)


def _fused_applies(cfg: AttentionConfig, S: int, window: int | None) -> bool:
    """Whether ``fused_causal_attention`` can take this self-attention call:
    plain causal, a tileable shape, and no multi-device mesh bound, neither
    the model's logical-axis rules nor JAX's mesh context."""
    return (
        cfg.causal
        and window is None
        and cfg.logit_softcap is None
        and _splash_block(S) is not None
        and cfg.head_dim in (64, 128, 256)
        and unsharded()
    )


def decode_attention(
    q: jax.Array,            # [B, 1, H, Dh]
    k_cache: jax.Array,      # [B, S, Hk, Dh]
    v_cache: jax.Array,
    length: jax.Array | int, # valid cache length (inclusive of current token)
    *,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,   # None: 1/sqrt(Dh)
) -> jax.Array:
    """Single-token attention against a cache — one matmul pass, fp32
    softmax. Memory-bound by the cache read (the roofline term that
    dominates decode shapes)."""
    B, _, H, Dh = q.shape
    _, S, Hk, _ = k_cache.shape
    G = H // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Hk, G, Dh) * scale
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k_cache, preferred_element_type=jnp.float32
    )
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(S)
    valid = pos[None, :] < (
        length if isinstance(length, jax.Array) else jnp.full((B,), length)
    )[:, None]
    if window is not None:
        cur = (
            length if isinstance(length, jax.Array) else jnp.full((B,), length)
        )[:, None]
        valid &= pos[None, :] > cur - 1 - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache, preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


# --------------------------------------------------------------- module
@dataclass
class KVCache:
    """Cache spec helper: full caches for global layers, ring buffers of
    ``window`` slots for sliding-window layers (what makes gemma3-style
    5:1 interleaves cheap at 500k)."""

    k: jax.Array
    v: jax.Array


def attn_init(key, cfg: AttentionConfig, d_model: int, layout: Layout, eps: float):
    H, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["wq"], s["wq"] = dense_init(ks[0], d_model, H * Dh, FSDP, TP, layout)
    p["wk"], s["wk"] = dense_init(ks[1], d_model, Hk * Dh, FSDP, TP, layout)
    p["wv"], s["wv"] = dense_init(ks[2], d_model, Hk * Dh, FSDP, TP, layout)
    p["wo"], s["wo"] = dense_init(ks[3], H * Dh, d_model, TP, FSDP, layout)
    if cfg.qk_norm:
        p["q_norm"], s["q_norm"] = norm_init(Dh, layout)
        p["k_norm"], s["k_norm"] = norm_init(Dh, layout)
    return p, s


def _project_qkv(p, cfg: AttentionConfig, x, positions, theta, eps):
    B, S, D = x.shape
    H, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (x @ p["wk"]).reshape(B, S, Hk, Dh)
    v = (x @ p["wv"]).reshape(B, S, Hk, Dh)
    if cfg.qk_norm:
        q = qk_head_norm(q, p["q_norm"], eps)
        k = qk_head_norm(k, p["k_norm"], eps)
    if cfg.rope:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def attn_apply(
    p,
    cfg: AttentionConfig,
    x: jax.Array,                  # [B, S, D]
    *,
    local: bool,
    eps: float,
    positions: jax.Array | None = None,
) -> jax.Array:
    """Training/prefill self-attention."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    theta = cfg.rope_theta_local if local else cfg.rope_theta
    q, k, v = _project_qkv(p, cfg, x, positions, theta, eps)
    q = shard_hint(q, DP, None, TP, None)
    if cfg.kv_replicate_hint:
        k = shard_hint(k, DP, None, None, None)
        v = shard_hint(v, DP, None, None, None)
    window = cfg.sliding_window if local else None

    def chunked(q, k, v):
        with jax.named_scope("attn.chunked"):
            return chunked_attention(
                q, k, v,
                causal=cfg.causal,
                window=window,
                q_chunk=cfg.q_chunk,
                kv_chunk=cfg.kv_chunk,
                softcap=cfg.logit_softcap,
                scale=cfg.scale,
            )

    def fused(q, k, v):
        with jax.named_scope("attn.flash"):
            return fused_causal_attention(q, k, v, scale=cfg.scale)

    if _fused_applies(cfg, S, window):
        # chosen when the step is lowered: the kernel on TPU, XLA elsewhere
        out = jax.lax.platform_dependent(q, k, v, tpu=fused, default=chunked)
    else:
        out = chunked(q, k, v)
    return out.reshape(B, S, -1) @ p["wo"]


def attn_decode(
    p,
    cfg: AttentionConfig,
    x: jax.Array,                  # [B, 1, D]
    cache_k: jax.Array,            # [B, S_cache, Hk, Dh]  (ring if local)
    cache_v: jax.Array,
    length: jax.Array,             # [B] current position (tokens so far)
    *,
    local: bool,
    eps: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step: insert the new k/v, attend over the cache.

    Local layers use a ring buffer: slot = length % cache_len. Returns
    (out [B,1,D], new_k, new_v).
    """
    B = x.shape[0]
    theta = cfg.rope_theta_local if local else cfg.rope_theta
    q, k, v = _project_qkv(p, cfg, x, length[:, None], theta, eps)
    S_cache = cache_k.shape[1]
    if local:
        slot = length % S_cache                       # ring buffer
    else:
        slot = jnp.minimum(length, S_cache - 1)
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, slot].set(k[:, 0])
    cache_v = cache_v.at[bidx, slot].set(v[:, 0])
    if local:
        # ring buffer: every live slot is within the window by construction
        mask_len = jnp.minimum(length + 1, S_cache)
        out = decode_attention(q, cache_k, cache_v, mask_len, window=None,
                               softcap=cfg.logit_softcap, scale=cfg.scale)
    else:
        out = decode_attention(q, cache_k, cache_v, length + 1, window=None,
                               softcap=cfg.logit_softcap, scale=cfg.scale)
    return out.reshape(B, 1, -1) @ p["wo"], cache_k, cache_v


def attn_cache_shape(cfg: AttentionConfig, batch: int, seq_len: int, local: bool,
                     dtype) -> tuple[tuple, tuple]:
    S = min(cfg.sliding_window, seq_len) if (local and cfg.sliding_window) else seq_len
    shape = (batch, S, cfg.num_kv_heads, cfg.head_dim)
    return shape, dtype
