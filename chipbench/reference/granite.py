"""Plain reference of the granite dense decoder as configured, with its
AdamW: ``jax.numpy`` in float32, every matrix product at HIGHEST
precision, no kernels and nothing imported from the program.

It follows the configuration file: GQA attention with rope on adjacent
dimension pairs, RMSNorm before attention and MLP, a SwiGLU MLP, an
untied output head over the padded vocabulary with the padding masked.
Parameters are stored as the configuration states (matrices and
embeddings bfloat16, norm scales float32) and upcast to float32 for
every computation; the optimizer's moments are float32.

``mode="fp8"`` is the control: every matrix product takes its operands
through float8_e4m3fn with one scale per tensor, the nearest precision
below the configuration's bfloat16.

The weights are the benchmark's own (``param_specs``/``make_params``),
drawn from the seed: the driver hands the same values to the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


# ------------------------------------------------------------------ weights
def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def vocab_padded(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def param_specs(cfg: dict) -> dict:
    """Leaf path -> (shape, stored dtype, init, scale), in the layout the
    program stores: layers stacked on a leading axis under
    ``stack/pat0``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, q = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n, vp = cfg["num_hidden_layers"], vocab_padded(cfg)
    w = cfg["train"]["param_dtype"]
    nd = cfg["train"]["norm_dtype"]
    return {
        "embed": ((vp, d), w, "normal", 1 / math.sqrt(d)),
        "lm_head": ((d, vp), w, "truncated", 1 / math.sqrt(d)),
        "final_norm": ((d,), nd, "ones", 1.0),
        "stack/pat0/norm1": ((n, d), nd, "ones", 1.0),
        "stack/pat0/norm2": ((n, d), nd, "ones", 1.0),
        "stack/pat0/mixer/wq": ((n, d, h * q), w, "truncated", 1 / math.sqrt(d)),
        "stack/pat0/mixer/wk": ((n, d, hk * q), w, "truncated", 1 / math.sqrt(d)),
        "stack/pat0/mixer/wv": ((n, d, hk * q), w, "truncated", 1 / math.sqrt(d)),
        "stack/pat0/mixer/wo": ((n, h * q, d), w, "truncated", 1 / math.sqrt(h * q)),
        "stack/pat0/ffn/wi": ((n, d, f), w, "truncated", 1 / math.sqrt(d)),
        "stack/pat0/ffn/wg": ((n, d, f), w, "truncated", 1 / math.sqrt(d)),
        "stack/pat0/ffn/wo": ((n, f, d), w, "truncated", 1 / math.sqrt(f)),
    }


def _leaf(key, shape, dtype, init, scale):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        return (jax.random.normal(key, shape, F32) * scale).astype(dtype)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) * scale).astype(dtype)


def make_params(key, specs: dict) -> dict:
    """Flat dict path -> array. Leaf i of the sorted paths draws from
    ``fold_in(key, i)``. Call under ``jax.jit``."""
    return {name: _leaf(jax.random.fold_in(key, i), *specs[name])
            for i, name in enumerate(sorted(specs))}


def change_norms(params: dict, key, specs: dict) -> dict:
    """Per-leaf L2 norm of ``params`` minus the initial weights, drawn
    again from ``key``. Call under ``jax.jit``."""
    init = make_params(key, specs)
    return {k: jnp.linalg.norm((params[k].astype(F32) - init[k].astype(F32)).ravel())
            for k in params}


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.linalg.norm(v.astype(F32).ravel()) for k, v in tree.items()}


# ------------------------------------------------------------------ model
def _fp8(x):
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _ein(spec, a, b, mode):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, S, H, Q]; rotates the pairs (0::2, 1::2)."""
    s, q = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, q, 2, dtype=F32) / q)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _layer(cfg, mode, x, p):
    b, s, d = x.shape
    h, hk, q = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    y = _rms(x, p["norm1"], eps)
    qh = _rope(_ein("bsd,de->bse", y, p["wq"], mode).reshape(b, s, h, q), cfg["rope_theta"])
    kh = _rope(_ein("bsd,de->bse", y, p["wk"], mode).reshape(b, s, hk, q), cfg["rope_theta"])
    vh = _ein("bsd,de->bse", y, p["wv"], mode).reshape(b, s, hk, q)
    kh = jnp.repeat(kh, h // hk, axis=2)   # query head i reads kv head i // (h / hk)
    vh = jnp.repeat(vh, h // hk, axis=2)
    scores = _ein("bqhe,bkhe->bhqk", qh * cfg["attention_multiplier"], kh, mode)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _ein("bhqk,bkhe->bqhe", probs, vh, mode).reshape(b, s, h * q)
    x = x + cfg["residual_multiplier"] * _ein("bse,ed->bsd", att, p["wo"], mode)
    y = _rms(x, p["norm2"], eps)
    act = jax.nn.silu(_ein("bsd,df->bsf", y, p["wg"], mode)) * _ein("bsd,df->bsf", y, p["wi"], mode)
    return x + cfg["residual_multiplier"] * _ein("bsf,fd->bsd", act, p["ffn_wo"], mode)


def loss_fn(params: dict, tokens, labels, cfg: dict, mode: str):
    """Mean next-token cross-entropy over every position of the batch.
    Each layer's weights are upcast inside its own (rematerialised) scan
    step, so one layer's float32 copy is live at a time; the gradient
    comes back in each leaf's stored dtype."""
    p = params
    x = p["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    stack = {
        "norm1": p["stack/pat0/norm1"], "norm2": p["stack/pat0/norm2"],
        "wq": p["stack/pat0/mixer/wq"], "wk": p["stack/pat0/mixer/wk"],
        "wv": p["stack/pat0/mixer/wv"], "wo": p["stack/pat0/mixer/wo"],
        "wi": p["stack/pat0/ffn/wi"], "wg": p["stack/pat0/ffn/wg"],
        "ffn_wo": p["stack/pat0/ffn/wo"],
    }

    def body(xc, lp):
        return _layer(cfg, mode, xc, {k: v.astype(F32) for k, v in lp.items()}), None

    x, _ = lax.scan(jax.checkpoint(body), x, stack)
    x = _rms(x, p["final_norm"].astype(F32), cfg["rms_norm_eps"])
    logits = _ein("bsd,dv->bsv", x, p["lm_head"].astype(F32), mode) / cfg["logits_scaling"]
    vp = logits.shape[-1]
    logits = jnp.where(jnp.arange(vp) < cfg["vocab_size"], logits, -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ------------------------------------------------------------------ AdamW
def _lr(t, tc: dict):
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    t = t.astype(F32)
    warm = jnp.minimum(t / tc["warmup_steps"], 1.0)
    frac = jnp.clip((t - tc["warmup_steps"]) / max(tc["steps"] - tc["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return tc["lr"] * warm * (tc["min_lr_ratio"] + (1 - tc["min_lr_ratio"]) * cos)


def train_step(state: dict, tokens, labels, *, cfg: dict, mode: str):
    """One AdamW step: global-norm clipping, bias-corrected moments,
    decoupled weight decay on stored leaves of rank 2 or more. Returns
    the new state, the loss and the per-leaf norms of the clipped
    gradient as the optimizer received it."""
    tc = cfg["train"]
    loss, g = jax.value_and_grad(loss_fn)(state["p"], tokens, labels, cfg, mode)
    g = {k: v.astype(F32) for k, v in g.items()}
    gnorm = jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))
    g = {k: v * jnp.minimum(1.0, tc["max_grad_norm"] / jnp.maximum(gnorm, 1e-9))
         for k, v in g.items()}
    t = state["t"] + 1
    lr = _lr(t, tc)
    b1, b2 = tc["b1"], tc["b2"]
    bc1, bc2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
    new = {"p": {}, "m": {}, "v": {}, "t": t}
    for k, p in state["p"].items():
        m = b1 * state["m"][k] + (1 - b1) * g[k]
        v = b2 * state["v"][k] + (1 - b2) * g[k] * g[k]
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + tc["eps"])
        if p.ndim >= 2:
            delta = delta + tc["weight_decay"] * p.astype(F32)
        new["p"][k] = (p.astype(F32) - lr * delta).astype(p.dtype)
        new["m"][k], new["v"][k] = m, v
    return new, loss, leaf_norms(g)


def readings(cfg: dict, seed: int, batches: list[dict], *, mode: str = "f32",
             batch_rows: slice | None = None) -> dict:
    """Run the reference from the seed's weights over ``batches``.
    Returns the losses, the first step's per-leaf gradient norms and the
    per-leaf norms of the change after the last step. ``batch_rows``
    keeps only those rows of each batch (a planted fault)."""
    specs = param_specs(cfg)
    key = seed_key(seed)
    params = jax.jit(partial(make_params, specs=specs))(key)
    state = {"p": params,
             "m": {k: jnp.zeros(v.shape, F32) for k, v in params.items()},
             "v": {k: jnp.zeros(v.shape, F32) for k, v in params.items()},
             "t": jnp.zeros((), jnp.int32)}
    step = jax.jit(partial(train_step, cfg=cfg, mode=mode), donate_argnums=0)
    losses, grad = [], None
    for b in batches:
        toks, labs = b["tokens"], b["labels"]
        if batch_rows is not None:
            toks, labs = toks[batch_rows], labs[batch_rows]
        state, loss, gn = step(state, jnp.asarray(toks), jnp.asarray(labs))
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(v) for k, v in gn.items()}
    change = jax.jit(partial(change_norms, specs=specs))(state["p"], key)
    out = {"losses": losses, "grad": grad, "change": {k: float(v) for k, v in change.items()}}
    del state
    return out


# ------------------------------------------------------------------ compare
def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared, each a gap between the program's reading and
    the reference's:

    - ``loss_gap``: the largest absolute gap of a step's loss;
    - ``grad_gap``: over leaves, the largest gap between the two norms of
      the first gradient, over the reference's norm of that leaf or of
      the median leaf, whichever is larger;
    - ``change_gap``: the same for the change after the last step, over
      the leaves whose reference gradient is at least a thousandth of
      the median leaf's (below that a leaf moves by round-off alone).
    """
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"], strict=True))
    g_med = float(np.median(list(ref["grad"].values())))
    grad_gap = max(abs(prog["grad"][k] - r) / max(r, g_med) for k, r in ref["grad"].items())
    moved = [k for k, r in ref["grad"].items() if r >= 1e-3 * g_med]
    c_med = float(np.median([ref["change"][k] for k in moved]))
    change_gap = max(abs(prog["change"][k] - ref["change"][k]) / max(ref["change"][k], c_med)
                     for k in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap}
