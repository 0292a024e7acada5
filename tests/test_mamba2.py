"""The Mamba-2 mixer and the Granite 4.0-H hybrid on the CPU, at small
sizes with seeded random weights: the chunked SSD against the plain
recurrence, the model's loss and gradients against the benchmark's plain
reference, prefill then decode against the full forward pass, and the
parameter count against the published config."""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import reduced
from repro.configs.base import Mamba2Config, get_config
from repro.models import mamba2
from repro.models.layers import Layout
from repro.models.transformer import LM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import granite_hybrid as ref  # noqa: E402

F32 = jnp.float32


def _plain_mixer(p, cfg: Mamba2Config, x, eps):
    """The mixer's equations one time step at a time, float32."""
    b, T, _ = x.shape
    H, P, N, G, K = cfg.n_heads, cfg.head_dim, cfg.d_state, cfg.n_groups, cfg.d_conv
    d_in = cfg.d_inner
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = zxbcdt[..., :d_in], zxbcdt[..., d_in:-H], zxbcdt[..., -H:]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, k:k + T] * p["conv_w"][k] for k in range(K)) + p["conv_b"])
    xs = xbc[..., :d_in].reshape(b, T, H, P)
    Bm = jnp.repeat(xbc[..., d_in:d_in + G * N].reshape(b, T, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., d_in + G * N:].reshape(b, T, G, N), H // G, axis=2)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    h = jnp.zeros((b, H, P, N), F32)
    ys = []
    for t in range(T):
        h = (jnp.exp(delta[:, t] * A)[..., None, None] * h
             + (delta[:, t, :, None] * xs[:, t])[..., None] * Bm[:, t, :, None, :])
        ys.append(jnp.einsum("bhpn,bhn->bhp", h, Cm[:, t]) + p["D"][:, None] * xs[:, t])
    y = jnp.stack(ys, axis=1).reshape(b, T, d_in) * jax.nn.silu(z)
    g = y.reshape(b, T, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(b, T, d_in) * p["norm"]) @ p["out_proj"], (xp[:, T:], h)


def test_chunked_mixer_matches_recurrence():
    """Three and a half chunks of 8 steps, two groups, float32 throughout.
    The chunked form sums the same terms in another order and takes each
    decay as one exp of a difference of cumulative sums rather than a
    product of per-step exps: rounding alone, so 2e-5 of the output's
    scale."""
    cfg = Mamba2Config(n_heads=4, head_dim=8, d_state=16, n_groups=2, d_conv=4, expand=2,
                       chunk=8)
    d, T = 16, 29
    layout = Layout(jnp.dtype(F32), jnp.dtype(F32))
    p, _ = mamba2.mamba2_init(jax.random.PRNGKey(0), cfg, d, layout)
    kb, kx = jax.random.split(jax.random.PRNGKey(1))
    p["conv_b"] = jax.random.normal(kb, p["conv_b"].shape) * 0.3
    x = jax.random.normal(kx, (2, T, d), F32)
    with jax.default_matmul_precision("highest"):
        got, (conv, h) = mamba2.mamba2_apply(p, cfg, x, eps=1e-5, return_state=True)
        want, (conv_w, h_w) = _plain_mixer(p, cfg, x, 1e-5)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(h, h_w, atol=2e-5 * float(jnp.max(jnp.abs(h_w))), rtol=0)
    np.testing.assert_array_equal(conv, conv_w)


# ------------------------------------------------------------------ the model
def _f32(cfg):
    return cfg.replace(param_dtype="float32", compute_dtype="float32")


def _ref_config(mcfg) -> dict:
    """The reference's configuration for a program ModelConfig."""
    a, m = mcfg.attention, mcfg.mamba2
    full = list(mcfg.pattern) * mcfg.n_periods + list(mcfg.remainder)
    return {
        "hidden_size": mcfg.d_model, "intermediate_size": mcfg.d_ff,
        "num_hidden_layers": mcfg.n_layers,
        "layer_types": ["attention" if e.startswith("attn") else "mamba" for e in full],
        "num_attention_heads": a.num_heads, "num_key_value_heads": a.num_kv_heads,
        "head_dim": a.head_dim, "vocab_size": mcfg.vocab_size,
        "mamba_n_heads": m.n_heads, "mamba_d_head": m.head_dim, "mamba_d_state": m.d_state,
        "mamba_n_groups": m.n_groups, "mamba_d_conv": m.d_conv,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_multiplier": a.scale, "embedding_multiplier": mcfg.embedding_multiplier,
        "residual_multiplier": mcfg.residual_multiplier, "logits_scaling": mcfg.logits_scaling,
        "rms_norm_eps": mcfg.norm_eps,
        "train": {"param_dtype": "float32", "norm_dtype": "float32", "max_grad_norm": 1e9},
    }


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def test_hybrid_loss_and_grads_match_reference():
    """The reduced hybrid (one period: 9 Mamba-2 layers, 1 NoPE attention
    layer) in float32 against the reference on the reference's own seeded
    weights. Both are float32 on the CPU; they differ in summation order
    (chunked SSD against the recurrence, flash-style against full
    attention, chunked against whole loss): 1e-5 on the loss, 1e-4 of
    each leaf's gradient norm."""
    mcfg = _f32(reduced(get_config("granite-4.0-h-micro")))
    rcfg = _ref_config(mcfg)
    specs = ref.param_specs(rcfg)
    flat = ref.make_params(ref.seed_key(5), specs)
    _, treedef = jax.tree_util.tree_flatten(LM.init(jax.random.PRNGKey(0), mcfg)[0])
    paths = [_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        LM.init(jax.random.PRNGKey(0), mcfg)[0])[0]]
    assert sorted(paths) == sorted(specs)
    params = jax.tree_util.tree_unflatten(treedef, [flat[k] for k in paths])

    B, S = 2, 40
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, S + 1), 0, mcfg.vocab_size)
    tokens, labels = toks[:, :-1], toks[:, 1:]

    def prog_loss(p):
        hidden, _ = LM.apply(p, mcfg, tokens)
        return LM.loss(p, mcfg, hidden, labels, seq_chunk=8)

    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(jax.value_and_grad(prog_loss))(params)
    want, rg = jax.jit(jax.value_and_grad(
        lambda f: ref.loss_fn(f, tokens, labels, rcfg, "f32")))(flat)
    np.testing.assert_allclose(float(loss), float(want), atol=1e-5, rtol=0)
    got = dict(zip(paths, jax.tree_util.tree_leaves(g), strict=True))
    for k in specs:
        n_got, n_want = float(jnp.linalg.norm(got[k])), float(jnp.linalg.norm(rg[k]))
        assert abs(n_got - n_want) <= 1e-4 * n_want + 1e-9, (k, n_got, n_want)


def test_hybrid_prefill_then_decode_matches_forward():
    """Prefill 20 tokens (a chunk of 16 and a padded one), then decode 6
    through the conv and SSM states and the attention layer's KV cache:
    each step's logits against the full forward pass's, float32, so 1e-4
    of the logits' scale (summation order only)."""
    cfg = _f32(reduced(get_config("granite-4.0-h-micro")))
    params, _ = LM.init(jax.random.PRNGKey(3), cfg)
    B, S0, S = 2, 20, 26
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        hidden, _ = LM.apply(params, cfg, toks)
        full = LM.logits(params, cfg, hidden)
        logits, caches, n = LM.prefill(params, cfg, toks[:, :S0], S)
        steps = [logits]
        for t in range(S0, S - 1):
            logits, caches = LM.decode_step(params, cfg, toks[:, t:t + 1], caches, n)
            n = n + 1
            steps.append(logits)
    got = jnp.stack(steps, axis=1)
    want = full[:, S0 - 1:S - 1, : cfg.vocab_size]
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got[..., : cfg.vocab_size], want, atol=1e-4 * scale, rtol=0)
    conv = caches["stack"]["pat0"]["conv"]
    assert conv.shape == (1, B, cfg.mamba2.d_conv - 1, cfg.mamba2.conv_dim)
    m = cfg.mamba2
    assert caches["stack"]["pat0"]["h"].shape == (1, B, m.n_heads, m.head_dim, m.d_state)


def test_param_count_of_the_published_config():
    """40 layers from the catalog's keys: 36 Mamba-2 mixers of
    in_proj 2048 x (4096 + 4352 + 64), conv 4 x 4352 plus bias, dt_bias,
    A_log and D of 64, the gated norm's 4096 and out_proj 4096 x 2048;
    4 attention mixers of 2048 x (2048 + 2 x 512) + 2048 x 2048; 40
    SwiGLU MLPs of 3 x 2048 x 8192; one tied 100352 x 2048 embedding.
    ``param_count`` leaves out the block norms' scales, as it does for
    every architecture."""
    cfg = get_config("granite-4.0-h-micro")
    d, v = 2048, 100352
    mamba = d * (4096 + 4352 + 64) + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * d
    attn = d * (32 * 64 + 2 * 8 * 64) + 32 * 64 * d
    mlp = 3 * d * 8192
    want = 36 * mamba + 4 * attn + 40 * mlp + v * d
    assert cfg.param_count() == want
    assert round((want + 40 * 2 * d + d) / 1e9, 2) == 3.19
    ten = dataclasses.replace(cfg, n_layers=10)
    assert round((ten.param_count() + 10 * 2 * d + d) / 1e6, 1) == 952.0


def test_mamba2_layers_and_caches():
    cfg = get_config("granite-4.0-h-micro")
    full = list(cfg.pattern) * cfg.n_periods
    assert [i for i, e in enumerate(full) if e.startswith("attn")] == [5, 15, 25, 35]
    caches = jax.eval_shape(lambda: LM.init_caches(cfg, 2, 64, jnp.bfloat16))
    assert caches["stack"]["pat0"]["conv"].shape == (4, 2, 3, 4352)
    assert caches["stack"]["pat0"]["h"].shape == (4, 2, 64, 64, 128)
    assert caches["stack"]["pat5"]["k"].shape == (4, 2, 64, 8, 64)
