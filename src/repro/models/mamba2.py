"""Mamba-2 mixer (the Granite 4.0-H 'mamba2' sublayer; SSD, arXiv:2405.21060).

    [z | xBC | dt] = x W_in
    xBC = silu(causal_depthwise_conv(xBC) + b_conv);  x, B, C = split(xBC)
    Δ = softplus(dt + dt_bias);  A = -exp(A_log)       one scalar per head
    h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t;  y_t = h_t C_t + D ⊙ x_t
    out = rms_gated(y · silu(z)) W_out                 norm over d_inner / G

Training and prefill run the chunked SSD form (``ssd``): inside each
chunk the masked ``(L ∘ C Bᵀ)(Δ x)`` matmuls, across chunks a scan that
carries the state. On one TPU the in-chunk term runs as the Pallas
kernel pair of ``kernels/ssd``, its decay masks kept in VMEM; elsewhere
as ``_intra_chunk`` in XLA. Decay math (Δ, the cumulative sums of Δ A,
their exponentials) and the carried state are float32; matmul operands take
the dtype of the mixer's input, accumulating in float32. Decode is the
one-step recurrence on a conv state [B, K-1, conv_dim] and an SSM state
[B, H, P, N] in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import Mamba2Config
from repro.distributed.sharding import FSDP, unsharded
from repro.kernels.ssd import ops as ssd_kernel
from repro.models.layers import Layout, dense_init

F32 = jnp.float32


def mamba2_init(key, cfg: Mamba2Config, d_model: int, layout: Layout):
    H, K = cfg.n_heads, cfg.d_conv
    if cfg.d_inner != cfg.expand * d_model:
        raise ValueError(f"n_heads * head_dim = {cfg.d_inner} != expand * d_model = "
                         f"{cfg.expand * d_model}")
    ks = jax.random.split(key, 5)
    p, s = {}, {}
    p["in_proj"], s["in_proj"] = dense_init(
        ks[0], d_model, cfg.d_inner + cfg.conv_dim + H, FSDP, None, layout)
    p["conv_w"] = (jax.random.normal(ks[1], (K, cfg.conv_dim)) / math.sqrt(K)
                   ).astype(layout.param_dtype)
    s["conv_w"] = (None, None)
    p["conv_b"] = jnp.zeros((cfg.conv_dim,), layout.param_dtype); s["conv_b"] = (None,)
    # Δ at init log-uniform in [1e-3, 1e-1]; dt_bias is its inverse softplus
    dt = jnp.exp(jax.random.uniform(ks[2], (H,)) * (math.log(0.1) - math.log(1e-3))
                 + math.log(1e-3))
    p["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(F32); s["dt_bias"] = (None,)
    p["A_log"] = jnp.log(jax.random.uniform(ks[3], (H,), minval=1.0, maxval=16.0))
    s["A_log"] = (None,)
    p["D"] = jnp.ones((H,), F32); s["D"] = (None,)
    p["norm"] = jnp.ones((cfg.d_inner,), F32); s["norm"] = (None,)
    p["out_proj"], s["out_proj"] = dense_init(ks[4], cfg.d_inner, d_model, None, FSDP, layout)
    return p, s


# ------------------------------------------------------------------ parts
def _in_proj(p, cfg: Mamba2Config, x):
    zxbcdt = x @ p["in_proj"]
    d_in = cfg.d_inner
    return (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + cfg.conv_dim],
            zxbcdt[..., d_in + cfg.conv_dim:])


def _conv(p, xbc, state=None):
    """Causal depthwise conv over time plus bias, then silu, in float32.
    ``state`` holds the K-1 inputs before ``xbc`` (zeros at the start).
    Returns the output in ``xbc``'s dtype and the last K-1 inputs."""
    K = p["conv_w"].shape[0]
    b, T, c = xbc.shape
    if state is None:
        state = jnp.zeros((b, K - 1, c), xbc.dtype)
    xp = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
    w = p["conv_w"].astype(F32)
    out = p["conv_b"].astype(F32)
    for i in range(K):
        out = out + xp[:, i:i + T].astype(F32) * w[i]
    return jax.nn.silu(out).astype(xbc.dtype), xp[:, T:]


def _split_xbc(cfg: Mamba2Config, xbc):
    """x [.., H, P], B and C [.., G, N]."""
    lead = xbc.shape[:-1]
    d_in, gn = cfg.d_inner, cfg.n_groups * cfg.d_state
    x = xbc[..., :d_in].reshape(*lead, cfg.n_heads, cfg.head_dim)
    B = xbc[..., d_in:d_in + gn].reshape(*lead, cfg.n_groups, cfg.d_state)
    C = xbc[..., d_in + gn:].reshape(*lead, cfg.n_groups, cfg.d_state)
    return x, B, C


def _gate_out(p, cfg: Mamba2Config, y, z, eps: float):
    """rms_gated(y · silu(z)) W_out, the norm over groups of d_inner / G."""
    with jax.named_scope("mamba2.gate_norm"):
        g = y.astype(F32) * jax.nn.silu(z.astype(F32))
        gg = g.reshape(*g.shape[:-1], cfg.n_groups, -1)
        gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True) + eps)
        y = (gg.reshape(g.shape) * p["norm"]).astype(z.dtype)
    with jax.named_scope("mamba2.out_proj"):
        return y @ p["out_proj"]


# ------------------------------------------------------------------ SSD
def _state_pass(states, chunk_decay):
    """The inter-chunk recurrence: ``h_c = decay_c h_{c-1} + states_c``.
    states: [b, c, ..., P, N]; chunk_decay: [b, c, ...]. Returns the state
    entering each chunk [b, c, ..., P, N] and the final state."""
    def step(h, inp):
        s_c, d_c = inp
        return d_c[..., None, None] * h + s_c, h

    h0 = jnp.zeros(states.shape[:1] + states.shape[2:], states.dtype)
    h_fin, h_in = jax.lax.scan(step, h0, (jnp.swapaxes(states, 0, 1),
                                          jnp.swapaxes(chunk_decay, 0, 1)))
    return jnp.swapaxes(h_in, 0, 1), h_fin


def _intra_chunk(C, B, cs, x, dt):
    """Inside each chunk: y_l = sum_{s<=l} (C_l . B_s) exp(cs_l - cs_s) Δ_s x_s.
    C, B [b, c, L, G, N] and x [b, c, L, G, J, P] in the matmul dtype;
    Δ and cs [b, c, L, G, J], cs the cumulative sums of Δ A. Returns y in
    cs's dtype, the masked product's operands cast to x's."""
    L = C.shape[2]
    ft, mt = cs.dtype, x.dtype
    xdt = (x.astype(ft) * dt[..., None]).astype(mt)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", C, B, preferred_element_type=ft)
    cst = jnp.moveaxis(cs, 2, -1)                       # [b, c, g, j, l]
    seg = cst[..., :, None] - cst[..., None, :]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))   # [b, c, g, j, l, s]
    m = (cb[:, :, :, None] * decay).astype(mt)
    return jnp.einsum("bcgjls,bcsgjp->bclgjp", m, xdt, preferred_element_type=ft)


def _intra_chunk_dispatch(C, B, cs, x, dt):
    """``_intra_chunk`` as the Pallas kernel pair (``kernels/ssd``) when the
    step is lowered for TPU and the shape tiles with no multi-device mesh
    bound; the XLA form everywhere else."""
    def xla(*args):
        with jax.named_scope("mamba2.ssd.xla"):
            return _intra_chunk(*args)

    def kernel(*args):
        with jax.named_scope("mamba2.ssd.kernel"):
            return ssd_kernel.ssd_intra(*args)

    _, _, L, G, N = C.shape
    J, P = x.shape[-2:]
    if cs.dtype == F32 and ssd_kernel.supported(L, G * J, P, N, G) and unsharded():
        return jax.lax.platform_dependent(C, B, cs, x, dt, tpu=kernel, default=xla)
    return xla(C, B, cs, x, dt)


def ssd(x, dt, A, B, C, chunk: int):
    """The SSD scan in chunks of ``chunk`` steps.

    x: [b, T, H, P]; dt: [b, T, H] (Δ, after softplus); A: [H];
    B, C: [b, T, G, N]. Decay math and accumulation run in ``dt``'s dtype,
    matmul operands in ``x``'s. Returns y [b, T, H, P] (without the D x
    skip) and the final state [b, H, P, N], both in ``dt``'s dtype.
    """
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    J = H // G
    ft, mt = dt.dtype, x.dtype
    L = min(chunk, T)
    nc = -(-T // L)
    if nc * L != T:   # Δ = 0 on the padding: the state neither decays nor takes input
        pad = nc * L - T
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    x = x.reshape(b, nc, L, G, J, P)
    dt = dt.reshape(b, nc, L, G, J)
    B = B.reshape(b, nc, L, G, N)
    C = C.reshape(b, nc, L, G, N)
    cs = jnp.cumsum(dt * A.reshape(G, J), axis=2)       # [b, c, l, g, j]

    y = _intra_chunk_dispatch(C, B, cs, x, dt)

    # each chunk's own contribution to the state at its end, then the pass
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt           # [b, c, l, g, j]
    xw = (x.astype(ft) * to_end[..., None]).astype(mt)
    states = jnp.einsum("bclgn,bclgjp->bcgjpn", B, xw, preferred_element_type=ft)
    h_in, h_fin = _state_pass(states, jnp.exp(cs[:, :, -1]))

    # the state entering each chunk, decayed to each step and read by C
    y_off = jnp.einsum("bclgn,bcgjpn->bclgjp", C, h_in.astype(mt),
                       preferred_element_type=ft)
    y = y + y_off * jnp.exp(cs)[..., None]
    y = y.reshape(b, nc * L, H, P)[:, :T]
    return y, h_fin.reshape(b, H, P, N)


# ------------------------------------------------------------------ entry points
def mamba2_apply(p, cfg: Mamba2Config, x, *, eps: float, return_state: bool = False):
    """Training/prefill. x: [B, T, D]. With ``return_state`` also returns
    (conv_state, ssm_state) for decode."""
    b, T, _ = x.shape
    with jax.named_scope("mamba2.in_proj"):
        z, xbc, dt = _in_proj(p, cfg, x)
    with jax.named_scope("mamba2.conv"):
        xbc, conv_state = _conv(p, xbc)
    xs, Bm, Cm = _split_xbc(cfg, xbc)
    with jax.named_scope("mamba2.ssd"):
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        y, h = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm, cfg.chunk)
        y = y + p["D"][:, None] * xs.astype(F32)
    out = _gate_out(p, cfg, y.reshape(b, T, cfg.d_inner), z, eps)
    if return_state:
        return out, (conv_state, h)
    return out


def mamba2_decode(p, cfg: Mamba2Config, x, state, *, eps: float):
    """One token. x: [B, 1, D]; state = (conv [B, K-1, conv_dim],
    h [B, H, P, N] float32)."""
    conv_state, h = state
    z, xbc, dt = _in_proj(p, cfg, x)
    xbc, conv_state = _conv(p, xbc, conv_state)
    xs, Bm, Cm = _split_xbc(cfg, xbc[:, 0])                 # [B, H, P], [B, G, N]
    J = cfg.n_heads // cfg.n_groups
    Bh = jnp.repeat(Bm.astype(F32), J, axis=1)              # head i reads group i // J
    Ch = jnp.repeat(Cm.astype(F32), J, axis=1)
    x0 = xs.astype(F32)
    dt = jax.nn.softplus(dt[:, 0].astype(F32) + p["dt_bias"])   # [B, H]
    dA = jnp.exp(dt * -jnp.exp(p["A_log"]))
    h = dA[..., None, None] * h + (dt[..., None] * x0)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch) + p["D"][:, None] * x0
    out = _gate_out(p, cfg, y.reshape(x.shape[0], 1, cfg.d_inner), z, eps)
    return out, (conv_state, h)
