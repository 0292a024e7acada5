"""RWKV-6 chunked WKV as a Pallas TPU kernel.

Grid: (batch, heads, chunks) — the chunk dimension is sequential; the
per-head state S ∈ R^{N×N} persists, transposed, in VMEM scratch across
chunk steps.
Each program loads one [L, N] chunk of r/k/v/log-decay, computes

    inter-chunk: (r ⊙ e^{Λ_prev}) @ S                 (MXU)
    intra-chunk: Σ_n r_t k_s e^{Λ_{t-1}−Λ_s} (s<t)    (VPU, bounded exps)
    diagonal:    (r·(u ⊙ k)) v
    state:       S ← e^{Λ_L} ⊙ S + (k e^{Λ_L−Λ})ᵀ V   (MXU)

All decay exponentials are of non-positive arguments (Λ is a cumsum of
log-decays ≤ 0), so fp32 is safe with no clamping. The intra term is
accumulated one [L, L] channel slice at a time; everything in VMEM is
KiB-scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, ST_ref, *, L: int, N: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        ST_ref[...] = jnp.zeros_like(ST_ref)

    r = r_ref[0, 0].astype(jnp.float32)          # [L, N]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)          # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)             # [1, N]
    ST = ST_ref[...]                             # Sᵀ: [N_v, N_k]

    # Λ_t inclusive, as a lower-triangular matmul (the TPU lowering has
    # no cumsum); HIGHEST keeps the fp32 sum off the bf16 MXU passes
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    lam = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    lam_prev = lam - w                           # Λ_{t-1}
    lam_end = lam[-1:, :]                        # Λ_L, [1, N]

    r_in = r * jnp.exp(lam_prev)
    o = jax.lax.dot_general(
        r_in, ST, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # [L, N]

    # one [L, L] slice of the intra tensor per channel: every operand
    # stays 2-D (the TPU lowering cannot insert a unit lane dim)
    tri = row > col
    kT, lamT = k.T, lam.T                        # [N, L]
    att = jnp.zeros((L, L), jnp.float32)
    for n in range(N):
        dl = lam_prev[:, n:n + 1] - lamT[n:n + 1, :]   # <= 0 for s < t
        att = att + jnp.where(tri, jnp.exp(dl), 0.0) * (
            r[:, n:n + 1] * kT[n:n + 1, :]
        )
    o = o + jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)
    o = o + diag * v

    k_out = k * jnp.exp(lam_end - lam)
    ST_ref[...] = jnp.exp(lam_end) * ST + jax.lax.dot_general(
        v, k_out, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0] = o.astype(o_ref.dtype)


def wkv6_kernel(
    r: jax.Array,        # [B, H, T, N]
    k: jax.Array,
    v: jax.Array,
    w_log: jax.Array,    # [B, H, T, N], log decay <= 0
    u: jax.Array,        # [H, N]
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    B, H, T, N = r.shape
    L = min(chunk, T)
    assert T % L == 0, f"T={T} % chunk={L}"
    nc = T // L
    grid = (B, H, nc)
    kern = functools.partial(_wkv6_kernel, L=L, N=N)
    spec = pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h, c, 0))
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            spec, spec, spec, spec,
            # [1, N] rows of a [H, 1, N] array: the block's last two
            # dims equal the array's, as the TPU lowering requires
            pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w_log, u.reshape(H, 1, N))
