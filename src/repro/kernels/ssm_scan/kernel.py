"""Mamba selective-scan as a Pallas TPU kernel.

Grid: (batch, channel_blocks, time_chunks) — time is sequential with the
SSM state h ∈ R^{dblk×N} carried in VMEM scratch; batch and channel
blocks are parallel. Within a chunk the recurrence

    h_t = e^{Δ_t A} h_{t-1} + (Δ_t x_t) B_t ;   y_t = h_t · C_t + D x_t

runs as a ``fori_loop`` over L steps of [N, dblk] vector work (VPU); the
O(T) dependency chain costs only T/L sequential *grid* steps of HBM
traffic. Each step indexes its own row of the input blocks, so no
[L, dblk, N] decay tensor is materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssm_kernel(dt_ref, x_ref, b_ref, c_ref, at_ref, d_ref, y_ref, h_ref,
                *, L: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    At = at_ref[...].astype(jnp.float32)          # [N, dblk]
    D = d_ref[...].astype(jnp.float32)            # [1, dblk]

    # time is a leading (untiled) block dim, so step t reads and writes
    # its rows by ref index; the state is kept transposed, [N, dblk], so
    # the contraction over N is a sublane reduction
    def step(t, h):
        dt = dt_ref[0, t].astype(jnp.float32)     # [1, dblk]
        x = x_ref[0, t].astype(jnp.float32)       # [1, dblk]
        b = b_ref[0, t].astype(jnp.float32)       # [N, 1]
        c = c_ref[0, t].astype(jnp.float32)       # [N, 1]
        h = jnp.exp(dt * At) * h + b * (dt * x)   # [N, dblk]
        y = jnp.sum(h * c, axis=0, keepdims=True) + D * x
        y_ref[0, t] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, L, step, h_ref[...])


def ssm_scan_kernel(
    dt: jax.Array,       # [B, T, d_in]
    x: jax.Array,        # [B, T, d_in]  (post-conv activations)
    Bm: jax.Array,       # [B, T, N]
    Cm: jax.Array,       # [B, T, N]
    A: jax.Array,        # [d_in, N]   (negative)
    D: jax.Array,        # [d_in]
    *,
    chunk: int = 64,
    dblk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, T, d_in = dt.shape
    N = Bm.shape[-1]
    L = min(chunk, T)
    dblk = min(dblk, d_in)
    assert T % L == 0 and d_in % dblk == 0
    nc, nd = T // L, d_in // dblk
    grid = (B, nd, nc)
    kern = functools.partial(_ssm_kernel, L=L)
    chan_spec = pl.BlockSpec((1, L, 1, dblk), lambda b, d, c: (b, c, 0, d))
    state_spec = pl.BlockSpec((1, L, N, 1), lambda b, d, c: (b, c, 0, 0))
    from jax.experimental.pallas import tpu as pltpu

    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            chan_spec,
            chan_spec,
            state_spec,
            state_spec,
            pl.BlockSpec((N, dblk), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, dblk), lambda b, d, c: (0, d)),
        ],
        out_specs=chan_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, 1, d_in), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, dblk), jnp.float32)],
        interpret=interpret,
    )(
        dt.reshape(B, T, 1, d_in),
        x.reshape(B, T, 1, d_in),
        Bm.reshape(B, T, N, 1),
        Cm.reshape(B, T, N, 1),
        A.T,
        D.reshape(1, d_in),
    )
    return y.reshape(B, T, d_in)
