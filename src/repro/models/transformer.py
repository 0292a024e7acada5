"""Model assembly: period-pattern layer stacks under ``lax.scan``.

A config's layer stack is ``pattern * n_periods + remainder``. All periods
share one traced body (compile time stays flat in depth); parameters are
stacked with a leading ``n_periods`` dim. Sublayer kinds:

    mixer: attn (global), local (sliding window), mamba, mamba2, rwkv,
           attnx (self+cross, whisper decoder)
    ffn:   mlp (SwiGLU), moe, rwkv (channel-mix)

Three entry points per model: ``apply`` (train/prefill logits),
``prefill`` (logits + caches), ``decode_step`` (one token with caches).
Cross-entropy is computed in sequence chunks so the [B,S,V] fp32 logits
tensor never materializes (mistral-large/llama4 vocabs would be tens of
GB otherwise).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import DP, FSDP, TP, shard_hint
from repro.models import attention as attn
from repro.models import mamba2 as mamba2_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    Layout,
    dense_init,
    embed_init,
    mlp_apply,
    mlp_init,
    norm_init,
    rms_norm,
    unembed_logits,
)

# ============================================================== sublayers
def _entry_init(key, entry: str, cfg: ModelConfig, layout: Layout):
    mixer, ffn = entry.split(":")
    kmix, kffn, kx = jax.random.split(key, 3)
    p: dict[str, Any] = {}
    s: dict[str, Any] = {}
    p["norm1"], s["norm1"] = norm_init(cfg.d_model, layout)
    p["norm2"], s["norm2"] = norm_init(cfg.d_model, layout)
    if mixer in ("attn", "local", "attnx"):
        p["mixer"], s["mixer"] = attn.attn_init(
            kmix, cfg.attention, cfg.d_model, layout, cfg.norm_eps
        )
        if mixer == "attnx":
            p["xnorm"], s["xnorm"] = norm_init(cfg.d_model, layout)
            p["xattn"], s["xattn"] = attn.attn_init(
                kx, cfg.attention, cfg.d_model, layout, cfg.norm_eps
            )
    elif mixer == "mamba":
        p["mixer"], s["mixer"] = ssm_mod.ssm_init(kmix, cfg.ssm, cfg.d_model, layout)
    elif mixer == "mamba2":
        p["mixer"], s["mixer"] = mamba2_mod.mamba2_init(kmix, cfg.mamba2, cfg.d_model, layout)
    elif mixer == "rwkv":
        p["mixer"], s["mixer"] = rwkv_mod.rwkv_block_init(
            kmix, cfg.rwkv, cfg.d_model, layout
        )
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ffn == "mlp":
        p["ffn"], s["ffn"] = mlp_init(kffn, cfg.d_model, cfg.d_ff, layout)
    elif ffn == "moe":
        p["ffn"], s["ffn"] = moe_mod.moe_init(kffn, cfg.moe, cfg.d_model, layout)
    elif ffn == "rwkv":
        p["ffn"], s["ffn"] = rwkv_mod.rwkv_ffn_init(
            kffn, cfg.d_model, cfg.d_ff, layout
        )
    else:
        raise ValueError(f"unknown ffn {ffn!r}")
    return p, s


def _residual(x, h, cfg: ModelConfig):
    """x + h, the branch scaled by the config's residual multiplier if set."""
    if cfg.residual_multiplier is not None:
        h = h * cfg.residual_multiplier
    return x + h


def _entry_apply(p, entry: str, cfg: ModelConfig, x, ctx) -> tuple[jax.Array, jax.Array]:
    """Pre-LN residual block. Returns (x, aux_loss)."""
    mixer, ffn = entry.split(":")
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer in ("attn", "local"):
        h = attn.attn_apply(
            p["mixer"], cfg.attention, h,
            local=(mixer == "local"), eps=cfg.norm_eps,
            positions=ctx.get("positions"),
        )
    elif mixer == "attnx":
        h = attn.attn_apply(
            p["mixer"], cfg.attention, h, local=False, eps=cfg.norm_eps,
            positions=ctx.get("positions"),
        )
        x = _residual(x, h, cfg)
        hx = rms_norm(x, p["xnorm"], cfg.norm_eps)
        h = _cross_attn_apply(p["xattn"], cfg, hx, ctx["encoder_out"])
    elif mixer == "mamba":
        h = ssm_mod.ssm_apply(p["mixer"], cfg.ssm, h)
    elif mixer == "mamba2":
        h = mamba2_mod.mamba2_apply(p["mixer"], cfg.mamba2, h, eps=cfg.norm_eps)
    elif mixer == "rwkv":
        h = rwkv_mod.rwkv_block_apply(p["mixer"], cfg.rwkv, h)
    x = _residual(x, h, cfg)
    x = shard_hint(x, DP, None, None)
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if ffn == "mlp":
        h = mlp_apply(p["ffn"], h, cfg.act)
    elif ffn == "moe":
        h, aux = moe_mod.moe_apply(p["ffn"], cfg.moe, h, cfg.act)
    elif ffn == "rwkv":
        h = rwkv_mod.rwkv_ffn_apply(p["ffn"], h)
    x = _residual(x, h, cfg)
    return shard_hint(x, DP, None, None), aux


def _cross_attn_apply(p, cfg: ModelConfig, x, enc_out):
    """Cross-attention: queries from x, keys/values from encoder output.
    No RoPE on cross attention (whisper-style absolute positions)."""
    a = cfg.attention
    B, S, D = x.shape
    H, Hk, Dh = a.num_heads, a.num_kv_heads, a.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (enc_out @ p["wk"]).reshape(B, enc_out.shape[1], Hk, Dh)
    v = (enc_out @ p["wv"]).reshape(B, enc_out.shape[1], Hk, Dh)
    o = attn.chunked_attention(
        q, k, v, causal=False, q_chunk=a.q_chunk, kv_chunk=a.kv_chunk
    )
    return o.reshape(B, S, H * Dh) @ p["wo"]


# -------------------------------------------------------------- caches
def _entry_cache_init(entry: str, cfg: ModelConfig, batch: int, cache_len: int,
                      dtype) -> dict:
    mixer, _ffn = entry.split(":")
    c: dict[str, Any] = {}
    if mixer in ("attn", "local", "attnx"):
        shape, dt = attn.attn_cache_shape(
            cfg.attention, batch, cache_len, mixer == "local", dtype
        )
        c["k"] = jnp.zeros(shape, dt)
        c["v"] = jnp.zeros(shape, dt)
        if mixer == "attnx":
            a = cfg.attention
            xl = cfg.encdec.cross_len_decode if cfg.encdec else 1500
            c["xk"] = jnp.zeros((batch, xl, a.num_kv_heads, a.head_dim), dt)
            c["xv"] = jnp.zeros((batch, xl, a.num_kv_heads, a.head_dim), dt)
    elif mixer == "mamba":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        c["conv"] = jnp.zeros((batch, s.d_conv - 1, d_in), dtype)
        c["h"] = jnp.zeros((batch, d_in, s.d_state), jnp.float32)
    elif mixer == "mamba2":
        m = cfg.mamba2
        c["conv"] = jnp.zeros((batch, m.d_conv - 1, m.conv_dim), dtype)
        c["h"] = jnp.zeros((batch, m.n_heads, m.head_dim, m.d_state), jnp.float32)
    elif mixer == "rwkv":
        r = cfg.rwkv
        H = cfg.d_model // r.head_size
        c["x_tm"] = jnp.zeros((batch, cfg.d_model), dtype)
        c["S"] = jnp.zeros((batch, H, r.head_size, r.head_size), jnp.float32)
    if entry.endswith(":rwkv"):
        c["x_cm"] = jnp.zeros((batch, cfg.d_model), dtype)
    return c


def _entry_decode(p, entry: str, cfg: ModelConfig, x, cache, lengths, ctx):
    """One-token step. x: [B,1,D]. Returns (x, new_cache)."""
    mixer, ffn = entry.split(":")
    new_cache = dict(cache)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer in ("attn", "local", "attnx"):
        h, nk, nv = attn.attn_decode(
            p["mixer"], cfg.attention, h, cache["k"], cache["v"], lengths,
            local=(mixer == "local"), eps=cfg.norm_eps,
        )
        new_cache["k"], new_cache["v"] = nk, nv
        if mixer == "attnx":
            x = _residual(x, h, cfg)
            hx = rms_norm(x, p["xnorm"], cfg.norm_eps)
            h = _cross_decode(p["xattn"], cfg, hx, cache["xk"], cache["xv"])
    elif mixer == "mamba":
        h, (nc, nh) = ssm_mod.ssm_decode(
            p["mixer"], cfg.ssm, h, (cache["conv"], cache["h"])
        )
        new_cache["conv"], new_cache["h"] = nc, nh
    elif mixer == "mamba2":
        h, (nc, nh) = mamba2_mod.mamba2_decode(
            p["mixer"], cfg.mamba2, h, (cache["conv"], cache["h"]), eps=cfg.norm_eps
        )
        new_cache["conv"], new_cache["h"] = nc, nh
    elif mixer == "rwkv":
        h, (nx, nS) = rwkv_mod.rwkv_block_decode(
            p["mixer"], cfg.rwkv, h, (cache["x_tm"], cache["S"])
        )
        new_cache["x_tm"], new_cache["S"] = nx, nS
    x = _residual(x, h, cfg)
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if ffn == "mlp":
        h = mlp_apply(p["ffn"], h, cfg.act)
    elif ffn == "moe":
        h, _ = moe_mod.moe_apply(p["ffn"], cfg.moe, h, cfg.act)
    elif ffn == "rwkv":
        h, nx = rwkv_mod.rwkv_ffn_decode(p["ffn"], h, cache["x_cm"])
        new_cache["x_cm"] = nx
    return _residual(x, h, cfg), new_cache


def _entry_prefill(p, entry: str, cfg: ModelConfig, x, cache_len: int, ctx):
    """Like _entry_apply but also builds the decode cache for this entry."""
    mixer, ffn = entry.split(":")
    c: dict[str, Any] = {}
    B, S, D = x.shape
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if mixer in ("attn", "local", "attnx"):
        a = cfg.attention
        positions = ctx.get("positions")
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        theta = a.rope_theta_local if mixer == "local" else a.rope_theta
        q, k, v = attn._project_qkv(p["mixer"], a, h, positions, theta, cfg.norm_eps)
        window = a.sliding_window if mixer == "local" else None
        o = attn.chunked_attention(
            q, k, v, causal=a.causal, window=window,
            q_chunk=a.q_chunk, kv_chunk=a.kv_chunk, softcap=a.logit_softcap,
            scale=a.scale,
        )
        h = o.reshape(B, S, -1) @ p["mixer"]["wo"]
        # build the cache
        if mixer == "local" and a.sliding_window and a.sliding_window < cache_len:
            W = a.sliding_window
            take = min(W, S)
            idx = (jnp.arange(S - take, S)) % W
            ck = jnp.zeros((B, W, a.num_kv_heads, a.head_dim), k.dtype)
            cv = jnp.zeros_like(ck)
            c["k"] = ck.at[:, idx].set(k[:, S - take:])
            c["v"] = cv.at[:, idx].set(v[:, S - take:])
        else:
            ck = jnp.zeros((B, cache_len, a.num_kv_heads, a.head_dim), k.dtype)
            cv = jnp.zeros_like(ck)
            c["k"] = jax.lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1)
            c["v"] = jax.lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1)
        if mixer == "attnx":
            x = _residual(x, h, cfg)
            hx = rms_norm(x, p["xnorm"], cfg.norm_eps)
            enc = ctx["encoder_out"]
            h = _cross_attn_apply(p["xattn"], cfg, hx, enc)
            xk = (enc @ p["xattn"]["wk"]).reshape(
                B, enc.shape[1], a.num_kv_heads, a.head_dim
            )
            xv = (enc @ p["xattn"]["wv"]).reshape(
                B, enc.shape[1], a.num_kv_heads, a.head_dim
            )
            c["xk"], c["xv"] = xk, xv
    elif mixer == "mamba":
        h, (conv, hs) = ssm_mod.ssm_apply(p["mixer"], cfg.ssm, h, return_state=True)
        c["conv"], c["h"] = conv, hs
    elif mixer == "mamba2":
        h, (conv, hs) = mamba2_mod.mamba2_apply(
            p["mixer"], cfg.mamba2, h, eps=cfg.norm_eps, return_state=True
        )
        c["conv"], c["h"] = conv, hs
    elif mixer == "rwkv":
        h, (x_tm, S_fin) = rwkv_mod.rwkv_block_prefill(p["mixer"], cfg.rwkv, h)
        c["x_tm"], c["S"] = x_tm, S_fin
    x = _residual(x, h, cfg)
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if ffn == "mlp":
        h2o = mlp_apply(p["ffn"], h2, cfg.act)
    elif ffn == "moe":
        h2o, _ = moe_mod.moe_apply(p["ffn"], cfg.moe, h2, cfg.act)
    elif ffn == "rwkv":
        h2o = rwkv_mod.rwkv_ffn_apply(p["ffn"], h2)
        c["x_cm"] = h2[:, -1, :]
    return _residual(x, h2o, cfg), c


def _cross_decode(p, cfg: ModelConfig, x, xk, xv):
    a = cfg.attention
    B, _, D = x.shape
    q = (x @ p["wq"]).reshape(B, 1, a.num_heads, a.head_dim)
    o = attn.decode_attention(q, xk, xv, xk.shape[1])
    return o.reshape(B, 1, a.num_heads * a.head_dim) @ p["wo"]


# ============================================================== stacks
def _stack_init(key, entries: tuple[str, ...], n: int, cfg: ModelConfig,
                layout: Layout):
    """Stack each pattern position's params over n periods (leading dim)."""
    p, s = {}, {}
    for pos, entry in enumerate(entries):
        keys = jax.random.split(jax.random.fold_in(key, pos), n)
        p[f"pat{pos}"] = jax.vmap(
            lambda k, e=entry: _entry_init(k, e, cfg, layout)[0]
        )(keys)
        # specs are identical across periods: prepend the periods dim (None)
        spec_one = _entry_init(jax.random.PRNGKey(0), entry, cfg, layout)[1]
        s[f"pat{pos}"] = jax.tree.map(
            lambda sp: (None, *sp),
            spec_one,
            is_leaf=lambda v: isinstance(v, tuple) and all(
                a is None or isinstance(a, (str, tuple)) for a in v
            ),
        )
    return p, s


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


# ============================================================== the model
class LM:
    """Functional decoder-only (or encoder-decoder) language model."""

    @staticmethod
    def init(key, cfg: ModelConfig):
        layout = Layout.from_config(cfg)
        keys = jax.random.split(key, 8)
        p: dict[str, Any] = {}
        s: dict[str, Any] = {}
        p["embed"], s["embed"] = embed_init(
            keys[0], cfg.vocab_padded, cfg.d_model, layout
        )
        if not cfg.tie_embeddings:
            p["lm_head"], s["lm_head"] = dense_init(
                keys[1], cfg.d_model, cfg.vocab_padded, FSDP, TP, layout
            )
        p["final_norm"], s["final_norm"] = norm_init(cfg.d_model, layout)
        if cfg.n_periods > 0:
            p["stack"], s["stack"] = _stack_init(
                keys[2], cfg.pattern, cfg.n_periods, cfg, layout
            )
        for i, entry in enumerate(cfg.remainder):
            p[f"rem{i}"], s[f"rem{i}"] = _entry_init(
                jax.random.fold_in(keys[3], i), entry, cfg, layout
            )
        if cfg.encdec is not None:
            ed = cfg.encdec
            enc_entries = ("attn:mlp",) * ed.n_encoder_layers
            p["enc_stack"], s["enc_stack"] = _stack_init(
                keys[4], ("attn:mlp",), ed.n_encoder_layers, cfg, layout
            )
            p["enc_norm"], s["enc_norm"] = norm_init(cfg.d_model, layout)
            del enc_entries
        return p, s

    # ---------------------------------------------------------- embedding
    @staticmethod
    def embed_tokens(p, cfg: ModelConfig, tokens, embeds=None):
        layout = Layout.from_config(cfg)
        x = jnp.take(p["embed"], tokens, axis=0).astype(layout.compute_dtype)
        mult = cfg.embedding_multiplier
        x = x * (math.sqrt(cfg.d_model) if mult is None else mult)
        if embeds is not None:
            x = jnp.concatenate([embeds.astype(layout.compute_dtype), x], axis=1)
        return shard_hint(x, DP, None, None)

    # ---------------------------------------------------------- encoder
    @staticmethod
    def encode(p, cfg: ModelConfig, frames):
        """Bidirectional encoder over stub frame embeddings [B, S, D]."""
        layout = Layout.from_config(cfg)
        x = frames.astype(layout.compute_dtype)
        x = x + _sinusoidal(x.shape[1], cfg.d_model, x.dtype)
        cfg_enc = cfg.replace(
            attention=cfg.attention and
            _dc_replace(cfg.attention, causal=False)
        )
        ctx = {"positions": None, "encoder_out": None}

        def body(xc, params):
            y, _ = _entry_apply(params, "attn:mlp", cfg_enc, xc, ctx)
            return y, None

        if cfg.unroll_stack:
            wrapped = _remat_wrap(body, cfg)
            n_enc = cfg.encdec.n_encoder_layers
            for i in range(n_enc):
                x, _ = wrapped(x, _tree_index(p["enc_stack"]["pat0"], i))
        else:
            x, _ = jax.lax.scan(
                _remat_wrap(body, cfg), x, p["enc_stack"]["pat0"]
            )
        return rms_norm(x, p["enc_norm"], cfg.norm_eps)

    # ---------------------------------------------------------- forward
    @staticmethod
    def backbone(p, cfg: ModelConfig, x, encoder_out=None, positions=None):
        """Residual stream through the full layer stack. x: [B,S,D]."""
        if positions is None:
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        ctx = {"positions": positions, "encoder_out": encoder_out}
        aux_total = jnp.zeros((), jnp.float32)
        if cfg.n_periods > 0:
            # a period of several sublayers rematerialises each one, so the
            # backward pass holds one sublayer's activations at a time
            remat_each = len(cfg.pattern) > 1

            def sublayer(entry):
                def fn(params, xc):
                    return _entry_apply(params, entry, cfg, xc, ctx)
                return _remat_wrap(fn, cfg) if remat_each else fn

            sublayers = [sublayer(entry) for entry in cfg.pattern]

            def period_body(carry, params):
                xc, aux = carry
                for pos, fn in enumerate(sublayers):
                    xc, a = fn(params[f"pat{pos}"], xc)
                    aux = aux + a
                return (xc, aux), None

            body = period_body if remat_each else _remat_wrap(period_body, cfg)
            if cfg.unroll_stack:
                for i in range(cfg.n_periods):
                    (x, aux_total), _ = body(
                        (x, aux_total), _tree_index(p["stack"], i)
                    )
            else:
                (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), p["stack"])
        for i, entry in enumerate(cfg.remainder):
            x, a = _entry_apply(p[f"rem{i}"], entry, cfg, x, ctx)
            aux_total = aux_total + a
        return rms_norm(x, p["final_norm"], cfg.norm_eps), aux_total

    @staticmethod
    def apply(p, cfg: ModelConfig, tokens, *, embeds=None, encoder_frames=None,
              positions=None):
        """Full forward returning (final_hidden, aux). Call ``loss`` or
        ``logits`` on the hidden state."""
        enc = (
            LM.encode(p, cfg, encoder_frames) if encoder_frames is not None else None
        )
        x = LM.embed_tokens(p, cfg, tokens, embeds)
        return LM.backbone(p, cfg, x, encoder_out=enc, positions=positions)

    # ---------------------------------------------------------- loss
    @staticmethod
    def unembed_table(p, cfg: ModelConfig):
        return p["embed"] if cfg.tie_embeddings else p["lm_head"]

    @staticmethod
    def loss(p, cfg: ModelConfig, hidden, labels, mask=None, seq_chunk: int = 512):
        """Chunked CE over the sequence: [B,S,V] never materializes."""
        B, S, D = hidden.shape
        table = LM.unembed_table(p, cfg)
        nchunk = -(-S // seq_chunk)
        pad = nchunk * seq_chunk - S
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            m = jnp.pad(
                jnp.ones((B, S), jnp.float32) if mask is None else mask,
                ((0, 0), (0, pad)),
            )
        else:
            m = jnp.ones((B, S), jnp.float32) if mask is None else mask

        hs = hidden.reshape(B, nchunk, seq_chunk, D).transpose(1, 0, 2, 3)
        ls = labels.reshape(B, nchunk, seq_chunk).transpose(1, 0, 2)
        ms = m.reshape(B, nchunk, seq_chunk).transpose(1, 0, 2)

        vmask = _vocab_pad_mask(cfg)

        def chunk_body(acc, inp):
            h, lab, mk = inp
            logits = _scaled_logits(h, table, cfg) + vmask
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
            nll = (logz - gold) * mk
            return (acc[0] + jnp.sum(nll), acc[1] + jnp.sum(mk)), None

        # the backward pass needs each chunk's fp32 softmax; past
        # _LOSS_SAVE_BYTES for the whole batch, recompute it chunk by chunk
        saved = B * nchunk * seq_chunk * cfg.vocab_padded * 4
        body = jax.checkpoint(chunk_body) if saved > _LOSS_SAVE_BYTES else chunk_body
        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hs, ls, ms),
        )
        return tot / jnp.maximum(cnt, 1.0)

    @staticmethod
    def logits(p, cfg: ModelConfig, hidden):
        return _scaled_logits(hidden, LM.unembed_table(p, cfg), cfg) + _vocab_pad_mask(cfg)

    # ---------------------------------------------------------- caches
    @staticmethod
    def init_caches(cfg: ModelConfig, batch: int, cache_len: int, dtype):
        caches: dict[str, Any] = {}
        if cfg.n_periods > 0:
            def one(entry):
                return _entry_cache_init(entry, cfg, batch, cache_len, dtype)

            stack = {}
            for pos, entry in enumerate(cfg.pattern):
                c = one(entry)
                stack[f"pat{pos}"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (cfg.n_periods, *a.shape)
                    ).copy(),
                    c,
                )
            caches["stack"] = stack
        for i, entry in enumerate(cfg.remainder):
            caches[f"rem{i}"] = _entry_cache_init(entry, cfg, batch, cache_len, dtype)
        return caches

    @staticmethod
    def prefill(p, cfg: ModelConfig, tokens, cache_len: int, *, embeds=None,
                encoder_frames=None):
        """Forward pass that also builds decode caches. Returns
        (last-position logits [B, V], caches, n_prefilled [B])."""
        enc = (
            LM.encode(p, cfg, encoder_frames) if encoder_frames is not None else None
        )
        x = LM.embed_tokens(p, cfg, tokens, embeds)
        S = x.shape[1]
        ctx = {"positions": jnp.arange(S, dtype=jnp.int32)[None, :],
               "encoder_out": enc}
        caches: dict[str, Any] = {}
        if cfg.n_periods > 0:
            def body(xc, params):
                cache = {}
                for pos, entry in enumerate(cfg.pattern):
                    xc, cache[f"pat{pos}"] = _entry_prefill(
                        params[f"pat{pos}"], entry, cfg, xc, cache_len, ctx
                    )
                return xc, cache

            if cfg.unroll_stack:
                cs = []
                for i in range(cfg.n_periods):
                    x, c = body(x, _tree_index(p["stack"], i))
                    cs.append(c)
                caches["stack"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *cs
                )
            else:
                x, stack_caches = jax.lax.scan(body, x, p["stack"])
                caches["stack"] = stack_caches
        for i, entry in enumerate(cfg.remainder):
            x, caches[f"rem{i}"] = _entry_prefill(
                p[f"rem{i}"], entry, cfg, x, cache_len, ctx
            )
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        logits = LM.logits(p, cfg, x[:, -1:])[:, 0]
        n = jnp.full((tokens.shape[0],), S, jnp.int32)
        return logits, caches, n

    @staticmethod
    def decode_step(p, cfg: ModelConfig, token, caches, lengths):
        """token: [B, 1] int32; lengths: [B] tokens already in cache.
        Returns (logits [B, V] fp32, new caches)."""
        x = LM.embed_tokens(p, cfg, token)
        ctx: dict[str, Any] = {}
        new_caches = dict(caches)
        if cfg.n_periods > 0:
            def body(xc, scanned):
                params, cache = scanned
                for pos, entry in enumerate(cfg.pattern):
                    xc, cache[f"pat{pos}"] = _entry_decode(
                        params[f"pat{pos}"], entry, cfg, xc,
                        cache[f"pat{pos}"], lengths, ctx,
                    )
                return xc, cache

            if cfg.unroll_stack:
                ncs = []
                for i in range(cfg.n_periods):
                    x, c = body(
                        x,
                        (_tree_index(p["stack"], i),
                         _tree_index(caches["stack"], i)),
                    )
                    ncs.append(c)
                new_caches["stack"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *ncs
                )
            else:
                x, new_stack = jax.lax.scan(
                    body, x, (p["stack"], caches["stack"])
                )
                new_caches["stack"] = new_stack
        for i, entry in enumerate(cfg.remainder):
            x, new_caches[f"rem{i}"] = _entry_decode(
                p[f"rem{i}"], entry, cfg, x, caches[f"rem{i}"], lengths, ctx
            )
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        logits = LM.logits(p, cfg, x)[:, 0]
        return logits, new_caches


# ------------------------------------------------------------------ misc
# bytes of fp32 softmax that LM.loss keeps for the backward pass before it
# recomputes each chunk's logits instead
_LOSS_SAVE_BYTES = 2 << 30


def _scaled_logits(h, table, cfg: ModelConfig):
    """fp32 logits, divided by the config's logits scaling if set."""
    logits = unembed_logits(h, table)
    if cfg.logits_scaling is not None:
        logits = logits / cfg.logits_scaling
    return logits


def _tree_index(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _vocab_pad_mask(cfg: ModelConfig) -> jax.Array:
    """-inf additive mask over padded vocab rows (0 where real)."""
    Vp, V = cfg.vocab_padded, cfg.vocab_size
    if Vp == V:
        return jnp.zeros((Vp,), jnp.float32)
    return jnp.where(jnp.arange(Vp) >= V, -1e30, 0.0).astype(jnp.float32)


def _sinusoidal(S: int, D: int, dtype) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, D, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / D)
    pe = jnp.zeros((S, D), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe[None].astype(dtype)


def _dc_replace(obj, **kw):
    import dataclasses

    return dataclasses.replace(obj, **kw)
