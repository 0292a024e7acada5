"""Plain reference of the Granite 4.0-H hybrid (``granitemoehybrid``) as
configured, with its AdamW: ``jax.numpy`` in float32, every matrix
product at HIGHEST precision, no kernels and nothing imported from the
program.

Each layer is ``h = x + r · mixer(rms(x))``, ``out = h + r · mlp(rms(h))``
with ``r`` the residual multiplier; the mixer is the one ``layer_types``
names for that layer:

- ``attention``: GQA with no positional embedding (``nope``), scores
  scaled by ``attention_multiplier``, causal softmax, computed in blocks
  of query rows;
- ``mamba``: the Mamba-2 mixer, written as its recurrence and run as a
  sequential scan over time, in blocks of positions that carry the conv
  inputs and the state from one to the next:
  ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv(xBC) + b)``;
  ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = h_t C_t + D x_t``;
  ``out = rms_gated(y · silu(z)) W_out``, the norm over d_inner / G.

Embeddings are scaled by ``embedding_multiplier``; the head is the tied
embedding over the padded vocabulary, its logits divided by
``logits_scaling`` and the padding masked. Parameters are stored as the
configuration states (matrices, the conv and the embedding bfloat16;
norm scales and the per-head dt_bias, A_log and D float32), held on the
device as float32 copies of the stored values; the loss is
differentiated with respect to those, so the gradient is float32, and
each update rounds the new weight to its stored dtype (``_stored``). The
optimizer's moments are float32 and live on the host; each leaf is
updated on the device in turn, so the device holds the weights and the
gradient, both float32, and one leaf's moments at once.

Departures from the published model, each also in the configuration's
``assumed``: weights are random, drawn from the seed; the layers are the
first ``num_hidden_layers`` of ``layer_types``; weight decay applies to
every stored leaf of rank 2 or more (the program stacks each layer's
leaves on a leading axis, so that is every layer leaf); no dt clamping
(the published ``time_step_limit`` is (0, inf)).

``mode="fp8"`` is the control: every matrix product takes its operands
through float8_e4m3fn with one scale per tensor (per block, where the
product runs over blocks of positions).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference.granite import (  # noqa: F401  (gaps, seed_key: the interface)
    F32,
    _ein,
    _lr,
    _rms,
    gaps,
    leaf_norms,
    seed_key,
    vocab_padded,
)

ATTN_ROWS = 128    # query rows per attention block at most: their gcd with the sequence
LOSS_ROWS = 256    # sequence positions per block of the loss
MLP_ROWS = 2048    # sequence positions per block of the MLP at most: their gcd with the sequence
MAMBA_ROWS = 512   # the same per block of the Mamba-2 mixer
SCAN_SEG = 64      # time steps per rematerialised segment of the recurrence


# ------------------------------------------------------------------ weights
def _mamba_dims(cfg: dict) -> tuple[int, int, int, int, int]:
    """H, P, N, G and K."""
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"])


def layer_kinds(cfg: dict) -> list[str]:
    return cfg["layer_types"][: cfg["num_hidden_layers"]]


def param_specs(cfg: dict) -> dict:
    """Leaf path -> (shape, stored dtype, init, scale), in the layout the
    program stores: one period of ``len(layer_types)`` layers, layer i
    under ``stack/pat<i>`` with a leading axis of 1."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, q = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    H, P, N, G, K = _mamba_dims(cfg)
    d_in, conv = H * P, H * P + 2 * G * N
    w, nd = cfg["train"]["param_dtype"], cfg["train"]["norm_dtype"]
    specs = {
        "embed": ((vocab_padded(cfg), d), w, "normal", 1 / math.sqrt(d)),
        "final_norm": ((d,), nd, "ones", 1.0),
    }
    if len(layer_kinds(cfg)) != len(cfg["layer_types"]):
        raise ValueError("the program stacks whole periods of layer_types")
    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise ValueError("the reference has a conv bias and no projection biases")
    for i, kind in enumerate(layer_kinds(cfg)):
        pre = f"stack/pat{i}/"
        layer = {
            "norm1": ((d,), nd, "ones", 1.0),
            "norm2": ((d,), nd, "ones", 1.0),
            "ffn/wi": ((d, f), w, "truncated", 1 / math.sqrt(d)),
            "ffn/wg": ((d, f), w, "truncated", 1 / math.sqrt(d)),
            "ffn/wo": ((f, d), w, "truncated", 1 / math.sqrt(f)),
        }
        if kind == "attention":
            layer.update({
                "mixer/wq": ((d, h * q), w, "truncated", 1 / math.sqrt(d)),
                "mixer/wk": ((d, hk * q), w, "truncated", 1 / math.sqrt(d)),
                "mixer/wv": ((d, hk * q), w, "truncated", 1 / math.sqrt(d)),
                "mixer/wo": ((h * q, d), w, "truncated", 1 / math.sqrt(h * q)),
            })
        elif kind == "mamba":
            layer.update({
                "mixer/in_proj": ((d, d_in + conv + H), w, "truncated", 1 / math.sqrt(d)),
                "mixer/conv_w": ((K, conv), w, "normal", 1 / math.sqrt(K)),
                "mixer/conv_b": ((conv,), w, "truncated", 1 / math.sqrt(K)),
                "mixer/dt_bias": ((H,), nd, "dt_bias", 1.0),
                "mixer/A_log": ((H,), nd, "a_log", 1.0),
                "mixer/D": ((H,), nd, "ones", 1.0),
                "mixer/norm": ((d_in,), nd, "ones", 1.0),
                "mixer/out_proj": ((d_in, d), w, "truncated", 1 / math.sqrt(d_in)),
            })
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        specs.update({pre + k: ((1, *s), dt, init, sc) for k, (s, dt, init, sc) in layer.items()})
    return specs


def _stored(x, dtype):
    """``x`` rounded to ``dtype``. The rounding is an explicit
    ``reduce_precision`` before the cast: XLA may drop a cast to a
    narrower float that is cast back inside one computation (its default
    ``xla_allow_excess_precision``), and the TPU compiler does, which
    would leave a stored weight, or its update, unrounded."""
    fi = jnp.finfo(dtype)
    return lax.reduce_precision(x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant).astype(dtype)


def _leaf(key, shape, dtype, init, scale):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        return _stored(jax.random.normal(key, shape, F32) * scale, dtype)
    if init == "dt_bias":   # inverse softplus of Δ log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, F32) * (math.log(0.1) - math.log(1e-3))
                     + math.log(1e-3))
        return _stored(dt + jnp.log(-jnp.expm1(-dt)), dtype)
    if init == "a_log":     # A = -exp(A_log) uniform in [-16, -1]
        return _stored(jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)), dtype)
    return _stored(jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) * scale, dtype)


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


# ------------------------------------------------------------------ model
def _attention(cfg, mode, y, p):
    b, s, _ = y.shape
    h, hk, q = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    qh = _ein("bsd,de->bse", y, p["mixer/wq"], mode).reshape(b, s, h, q)
    kh = _ein("bsd,de->bse", y, p["mixer/wk"], mode).reshape(b, s, hk, q)
    vh = _ein("bsd,de->bse", y, p["mixer/wv"], mode).reshape(b, s, hk, q)
    kh = jnp.repeat(kh, h // hk, axis=2)   # query head i reads kv head i // (h / hk)
    vh = jnp.repeat(vh, h // hk, axis=2)
    rows = math.gcd(ATTN_ROWS, s)

    def block(_, i):
        qb = lax.dynamic_slice_in_dim(qh, i * rows, rows, axis=1)
        scores = _ein("bqhe,bkhe->bhqk", qb * cfg["attention_multiplier"], kh, mode)
        causal = (i * rows + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return None, _ein("bhqk,bkhe->bqhe", probs, vh, mode)

    _, out = lax.scan(jax.checkpoint(block), None, jnp.arange(s // rows))
    att = jnp.moveaxis(out, 0, 1).reshape(b, s, h * q)
    return _ein("bse,ed->bsd", att, p["mixer/wo"], mode)


def recurrence(x, dt, A, B, C, h, mode: str = "f32"):
    """``h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = h_t C_t``,
    one step at a time from the state ``h`` [b, H, P, N]. x: [b, s, H, P];
    dt: [b, s, H]; A: [H]; B, C: [b, s, G, N]. Time runs as a scan over
    segments of ``SCAN_SEG`` steps, each rematerialised, so the backward
    pass holds one segment's states. Returns y [b, s, H, P] and the state
    after the last step."""
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    x = x.reshape(b, s, G, H // G, P)      # head i reads group i // (H / G)
    dt = dt.reshape(b, s, G, H // G)
    A = A.reshape(G, H // G)
    seg = min(SCAN_SEG, s)
    nseg = -(-s // seg)
    pad = nseg * seg - s                   # Δ = 0, x = 0 on the padding: no effect

    def cut(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(b, nseg, seg, *a.shape[2:]), (1, 2), (0, 1))

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        u = _ein("bgjp,bgn->bgjpn", dt_t[..., None] * x_t, B_t, mode)
        h = jnp.exp(dt_t * A)[..., None, None] * h + u
        return h, _ein("bgjpn,bgn->bgjp", h, C_t, mode)

    def segment(h, inp):
        return lax.scan(step, h, inp)

    h, y = lax.scan(jax.checkpoint(segment), h.reshape(b, G, H // G, P, N),
                    tuple(cut(a) for a in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape(nseg * seg, b, H, P), 0, 1)[:, :s], h.reshape(b, H, P, N)


def _mamba_in(cfg, mode, y, p, conv_in):
    """z, and x, B, C and Δ of the recurrence. ``conv_in`` holds the K-1
    conv inputs before ``y``'s first position; also returns its last K-1."""
    b, s, _ = y.shape
    H, P, N, G, K = _mamba_dims(cfg)
    d_in = H * P
    zxbcdt = _ein("bsd,de->bse", y, p["mixer/in_proj"], mode)
    z, xbc, dt = zxbcdt[..., :d_in], zxbcdt[..., d_in:-H], zxbcdt[..., -H:]
    xp = jnp.concatenate([conv_in, xbc], axis=1)      # causal depthwise conv
    conv = sum(xp[:, k:k + s] * p["mixer/conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["mixer/conv_b"])
    xs = xbc[..., :d_in].reshape(b, s, H, P)
    Bm = xbc[..., d_in:d_in + G * N].reshape(b, s, G, N)
    Cm = xbc[..., d_in + G * N:].reshape(b, s, G, N)
    return (z, xs, Bm, Cm, jax.nn.softplus(dt + p["mixer/dt_bias"])), xp[:, s:]


def _mamba_out(cfg, mode, ys, xs, z, p):
    """rms_gated((y + D x) · silu(z)) W_out."""
    b, s, H, P = xs.shape
    G = cfg["mamba_n_groups"]
    ys = ys + p["mixer/D"][:, None] * xs
    g = (ys.reshape(b, s, H * P) * jax.nn.silu(z)).reshape(b, s, G, H * P // G)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    g = g.reshape(b, s, H * P) * p["mixer/norm"]
    return _ein("bse,ed->bsd", g, p["mixer/out_proj"], mode)


def _mamba(cfg, mode, y, p):
    """The mixer over blocks of ``MAMBA_ROWS`` positions in order, each
    rematerialised; the conv's last K-1 inputs and the state carry from
    block to block, so the backward pass holds one block's working set."""
    b, s, d = y.shape
    H, P, N, G, K = _mamba_dims(cfg)
    rows = math.gcd(MAMBA_ROWS, s)
    A = -jnp.exp(p["mixer/A_log"])

    def block(carry, yb):
        conv_in, h = carry
        (z, xs, Bm, Cm, delta), conv_in = _mamba_in(cfg, mode, yb, p, conv_in)
        ys, h = recurrence(xs, delta, A, Bm, Cm, h, mode)
        return (conv_in, h), _mamba_out(cfg, mode, ys, xs, z, p)

    carry = (jnp.zeros((b, K - 1, H * P + 2 * G * N), F32), jnp.zeros((b, H, P, N), F32))
    _, out = lax.scan(jax.checkpoint(block), carry,
                      jnp.moveaxis(y.reshape(b, s // rows, rows, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def _mlp(cfg, mode, y, p):
    """SwiGLU over blocks of ``MLP_ROWS`` positions, each rematerialised."""
    b, s, d = y.shape
    rows = math.gcd(MLP_ROWS, s)

    def block(yb):
        act = (jax.nn.silu(_ein("bsd,df->bsf", yb, p["ffn/wg"], mode))
               * _ein("bsd,df->bsf", yb, p["ffn/wi"], mode))
        return _ein("bsf,fd->bsd", act, p["ffn/wo"], mode)

    out = lax.map(jax.checkpoint(block), jnp.moveaxis(y.reshape(b, s // rows, rows, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def _layer(cfg, mode, kind, x, p):
    """One layer; its mixer and its MLP are each rematerialised, so the
    backward pass holds the working set of one of them at a time."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = jax.checkpoint(partial(_attention if kind == "attention" else _mamba, cfg, mode))
    x = x + r * mixer(_rms(x, p["norm1"], eps), p)
    return x + r * jax.checkpoint(partial(_mlp, cfg, mode))(_rms(x, p["norm2"], eps), p)


def loss_fn(params: dict, tokens, labels, cfg: dict, mode: str):
    """Mean next-token cross-entropy over every position of the batch.
    ``params`` are float32. Each layer is rematerialised, and the loss is
    taken over blocks of ``LOSS_ROWS`` positions, each rematerialised."""
    p = params
    x = p["embed"][tokens] * cfg["embedding_multiplier"]
    for i, kind in enumerate(layer_kinds(cfg)):
        pre = f"stack/pat{i}/"
        lp = {k[len(pre):]: v[0] for k, v in p.items() if k.startswith(pre)}
        x = jax.checkpoint(partial(_layer, cfg, mode, kind))(x, lp)
    x = _rms(x, p["final_norm"], cfg["rms_norm_eps"])
    b, s, d = x.shape
    rows = min(LOSS_ROWS, s)
    if s % rows:
        raise ValueError(f"sequence {s} is not a multiple of {rows} loss rows")
    xs = jnp.moveaxis(x.reshape(b, s // rows, rows, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, s // rows, rows), 1, 0)

    def block(total, inp):
        xb, lb = inp
        logits = _ein("bsd,vd->bsv", xb, p["embed"], mode) / cfg["logits_scaling"]
        logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg["vocab_size"], logits, -1e30)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return total + jnp.sum(logz - gold), None

    total, _ = lax.scan(jax.checkpoint(block), jnp.zeros((), F32), (xs, ls))
    return total / (b * s)


# ------------------------------------------------------------------ AdamW
def _grad(params: dict, tokens, labels, *, cfg: dict, mode: str):
    """Loss and float32 gradient at ``params`` (float32); the factor that
    clips it to ``max_grad_norm`` by the global norm, and each leaf's norm
    after clipping."""
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, labels, cfg, mode)
    gnorm = jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))
    clip = jnp.minimum(1.0, cfg["train"]["max_grad_norm"] / jnp.maximum(gnorm, 1e-9))
    return loss, g, clip, {k: n * clip for k, n in leaf_norms(g).items()}


def _update(p, g, clip, m, v, t, *, tc: dict, stored: str):
    """AdamW on one float32 leaf and its gradient times ``clip``:
    bias-corrected moments, decoupled weight decay on leaves of rank 2 or
    more. Returns the new weight in its ``stored`` dtype, and the moments."""
    g = g * clip
    b1, b2 = tc["b1"], tc["b2"]
    tf = t.astype(F32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + tc["eps"])
    if p.ndim >= 2:
        delta = delta + tc["weight_decay"] * p
    return _stored(p - _lr(t, tc) * delta, stored), m, v


def readings(cfg: dict, seed: int, batches: list[dict], *, mode: str = "f32",
             batch_rows: slice | None = None) -> dict:
    """Run the reference from the seed's weights over ``batches``.
    Returns the losses, the first step's per-leaf gradient norms and the
    per-leaf norms of the change after the last step. ``batch_rows``
    keeps only those rows of each batch (a planted fault)."""
    specs = param_specs(cfg)
    key = seed_key(seed)
    # the stored weights, held as float32 copies of the stored values: each
    # leaf leaves a computation in its stored dtype and is cast up by itself
    params = jax.jit(partial(make_params, specs=specs))(key)
    params = {k: params[k].astype(F32) for k in sorted(specs)}
    moments = {k: (np.zeros(s[0], np.float32), np.zeros(s[0], np.float32))
               for k, s in specs.items()}
    grad = jax.jit(partial(_grad, cfg=cfg, mode=mode))
    update = jax.jit(partial(_update, tc=cfg["train"]), static_argnames="stored",
                     donate_argnums=(3, 4))
    losses, first = [], None
    for t, b in enumerate(batches, start=1):
        toks, labs = b["tokens"], b["labels"]
        if batch_rows is not None:
            toks, labs = toks[batch_rows], labs[batch_rows]
        loss, g, clip, gn = grad(params, jnp.asarray(toks), jnp.asarray(labs))
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in gn.items()}
        for k in sorted(specs):
            m, v = moments[k]
            p, m, v = update(params.pop(k), g.pop(k), clip, jnp.asarray(m), jnp.asarray(v),
                             jnp.asarray(t, jnp.int32), stored=specs[k][1])
            params[k] = p.astype(F32)
            moments[k] = (np.asarray(m), np.asarray(v))
    change = jax.jit(partial(change_norms, specs=specs))(params, key)
    out = {"losses": losses, "grad": first,
           "change": {k: float(v) for k, v in change.items()}}
    del params
    return out
