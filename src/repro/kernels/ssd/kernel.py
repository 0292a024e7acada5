"""The Mamba-2 SSD's intra-chunk term as a pair of Pallas TPU kernels.

    y[l] = Σ_{s≤l} (C_l · B_s) exp(cs_l − cs_s) (Δx)_s        inside each chunk

Every operand is time-minor, with the steps on lanes: C and B [b, G·N, T],
x, y and their gradients [b, H, P, T], Δ [b, H, T], the cumulative sums
[b, T/L, H, L]. That is the layout XLA gives the rest of the mixer (its P
of 64 would half-fill the lanes), so no transposing copy sits between the
kernels and their neighbours; Δx is formed in VMEM for the same reason.

Grid: (batch, chunk, block of ``HEADS`` heads). A program holds one
chunk's Cᵀ and Bᵀ [N, L] of the group its heads read, the cumulative sums
of its heads [hb, L], their xᵀ [hb, P, L] and Δ [hb, L]. (C Bᵀ)ᵀ is
computed into VMEM scratch at the first head block of a group, so the
head-block axis runs in order. Each head's L×L decay mask is built
transposed, [s, l], in 128×128 tiles, the tiles below the diagonal
(s > l) skipped, and used and dropped in VMEM: no mask reaches HBM.

The arithmetic is the XLA form's: the mask is ``exp`` of the difference
of float32 cumulative sums under the causal mask (never factored into
``exp(cs_l)·exp(−cs_s)``), ``C Bᵀ ∘ mask`` is float32 and is cast to Δx's
dtype for the product, and every product accumulates in float32.

The backward kernel recomputes Δx and the mask. It rounds dΔx to x's dtype,
as XLA's cotangent of Δx is, and returns dx and dΔ through x·Δ, dcs (row
sums minus column sums of the masked d(seg)) and, at the last head block
of a group, dC and dB from d(C Bᵀ), accumulated over the group's heads in
scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
TILE = 128           # rows and columns of one mask tile
HEADS = 8            # heads a program holds

_NT = (((1,), (1,)), ((), ()))      # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _tile(i: int):
    return slice(i * TILE, (i + 1) * TILE)


def _decay(csr, csc, h: int, r: int, c: int):
    """exp(cs_l − cs_s) of head h on the tile of steps s in tile r and l in
    tile c (c >= r), [s, l], 0 where s > l."""
    seg = csr[h:h + 1, _tile(c)] - csc[_tile(r), h:h + 1]
    if r == c:
        s = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
        l = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
        seg = jnp.where(l >= s, seg, -jnp.inf)
    return jnp.exp(seg)


def _xdt(x_ref, dt_ref, h: int):
    """(Δx)ᵀ of head h, [P, L]: x·Δ in float32, cast to x's dtype."""
    return (x_ref[0, h].astype(F32) * dt_ref[0, h:h + 1, :]).astype(x_ref.dtype)


def _fwd_kernel(c_ref, b_ref, csr_ref, x_ref, dt_ref, y_ref, cbt_ref, *, hb: int, J: int):
    nt = cbt_ref.shape[0] // TILE
    mt = x_ref.dtype

    @pl.when((pl.program_id(2) * hb) % J == 0)
    def _cbt():
        cbt_ref[...] = _dot(b_ref[0], c_ref[0], _TN)           # [s, l]

    csr = csr_ref[0, 0]                                        # [hb, L]
    csc = csr.T                                                # [L, hb]
    for h in range(hb):
        xdt = _xdt(x_ref, dt_ref, h)                           # [P, L]
        for c in range(nt):                                    # l
            y = jnp.zeros((xdt.shape[0], TILE), F32)
            for r in range(c + 1):                             # s <= l
                m = cbt_ref[_tile(r), _tile(c)] * _decay(csr, csc, h, r, c)
                y = y + _dot(xdt[:, _tile(r)], m.astype(mt))
            y_ref[0, h, :, _tile(c)] = y


def _bwd_kernel(c_ref, b_ref, csr_ref, x_ref, dt_ref, dy_ref,
                dc_ref, db_ref, dcs_ref, dx_ref, ddt_ref, cbt_ref, dcbt_ref, *, hb: int, J: int):
    nt = cbt_ref.shape[0] // TILE
    mt = x_ref.dtype
    k = pl.program_id(2)

    @pl.when((k * hb) % J == 0)
    def _cbt():
        cbt_ref[...] = _dot(b_ref[0], c_ref[0], _TN)           # [s, l]
        dcbt_ref[...] = jnp.zeros_like(dcbt_ref)

    csr = csr_ref[0, 0]                                        # [hb, L]
    csc = csr.T                                                # [L, hb]
    sub = jax.lax.broadcasted_iota(jnp.int32, (hb, TILE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (TILE, hb), 1)
    dcsr = [jnp.zeros((hb, TILE), F32) for _ in range(nt)]     # Σ_s dseg, by l
    dcsc = [jnp.zeros((TILE, hb), F32) for _ in range(nt)]     # −Σ_l dseg, by s
    ddt = [jnp.zeros((hb, TILE), F32) for _ in range(nt)]
    for h in range(hb):
        x = x_ref[0, h].astype(F32)                            # [P, L]
        xdt = _xdt(x_ref, dt_ref, h)
        dy = dy_ref[0, h].astype(mt)
        for r in range(nt):                                    # s
            dxdt = jnp.zeros((x.shape[0], TILE), F32)
            for c in range(r, nt):                             # l >= s
                decay = _decay(csr, csc, h, r, c)
                mf = cbt_ref[_tile(r), _tile(c)] * decay
                dxdt = dxdt + _dot(dy[:, _tile(c)], mf.astype(mt), _NT)
                dm = _dot(xdt[:, _tile(r)], dy[:, _tile(c)], _TN)  # [s, l]
                dcbt_ref[_tile(r), _tile(c)] += dm * decay
                dseg = dm * mf
                dcsr[c] = dcsr[c] + jnp.where(
                    sub == h, jnp.sum(dseg, axis=0, keepdims=True), 0.0)
                dcsc[r] = dcsc[r] - jnp.where(
                    lane == h, jnp.sum(dseg, axis=1, keepdims=True), 0.0)
            # the cotangent of Δx in Δx's dtype, then back through x·Δ
            dxdt = dxdt.astype(mt).astype(F32)
            dt = dt_ref[0, h:h + 1, _tile(r)]
            dx_ref[0, h, :, _tile(r)] = (dxdt * dt).astype(dx_ref.dtype)
            ddt[r] = ddt[r] + jnp.where(
                sub == h, jnp.sum(dxdt * x[:, _tile(r)], axis=0, keepdims=True), 0.0)
    for t in range(nt):
        dcs_ref[0, 0, :, _tile(t)] = dcsr[t] + dcsc[t].T
        ddt_ref[0, :, _tile(t)] = ddt[t]

    @pl.when(((k + 1) * hb) % J == 0)
    def _dcb():
        dcbt = dcbt_ref[...].astype(mt)                        # d(C Bᵀ)ᵀ: [s, l]
        db_ref[0] = _dot(c_ref[0], dcbt, _NT).astype(db_ref.dtype)    # dBᵀ: [N, s]
        dc_ref[0] = _dot(b_ref[0], dcbt).astype(dc_ref.dtype)         # dCᵀ: [N, l]


def _specs(L: int, N: int, hb: int, J: int, P: int):
    """Block specs of Cᵀ/Bᵀ, the cumulative sums, x-shaped and Δ-shaped arrays."""
    group = pl.BlockSpec((1, N, L), lambda b, c, k: (b, (k * hb) // J, c))
    rows = pl.BlockSpec((1, 1, hb, L), lambda b, c, k: (b, c, k, 0))
    heads = pl.BlockSpec((1, hb, P, L), lambda b, c, k: (b, k, 0, c))
    steps = pl.BlockSpec((1, hb, L), lambda b, c, k: (b, k, c))
    return group, rows, heads, steps


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))

# Under ``jax.jit`` a kernel body is traced once per shape: a bare
# ``pallas_call`` traces it again at each of the model's call sites, which
# costs the step's set-up tens of seconds on the chip host.


@functools.partial(jax.jit, static_argnames=("L", "J", "interpret"))
def ssd_intra_fwd(C, B, cs, x, dt, *, L: int, J: int, interpret: bool = False):
    """C, B [b, G·N, T]; cs [b, T/L, H, L] float32; x [b, H, P, T]; Δ [b, H, T]
    float32. Returns y [b, H, P, T] float32."""
    b, H, P, T = x.shape
    N = C.shape[1] // (H // J)
    group, rows, heads, steps = _specs(L, N, HEADS, J, P)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=HEADS, J=J),
        grid=(b, T // L, H // HEADS),
        in_specs=[group, group, rows, heads, steps],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(x.shape, F32),
        scratch_shapes=[pltpu.VMEM((L, L), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_intra_fwd",
    )(C, B, cs, x, dt)


@functools.partial(jax.jit, static_argnames=("L", "J", "interpret"))
def ssd_intra_bwd(C, B, cs, x, dt, dy, *, L: int, J: int, interpret: bool = False):
    """The forward's inputs and dy [b, H, P, T] float32. Returns dC, dB, dcs, dx
    and dΔ in their inputs' layouts and dtypes."""
    b, H, P, T = x.shape
    N = C.shape[1] // (H // J)
    group, rows, heads, steps = _specs(L, N, HEADS, J, P)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=HEADS, J=J),
        grid=(b, T // L, H // HEADS),
        in_specs=[group, group, rows, heads, steps, heads],
        out_specs=[group, group, rows, heads, steps],
        out_shape=[jax.ShapeDtypeStruct(C.shape, C.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(cs.shape, F32),
                   jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, F32)],
        scratch_shapes=[pltpu.VMEM((L, L), F32), pltpu.VMEM((L, L), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_intra_bwd",
    )(C, B, cs, x, dt, dy)
