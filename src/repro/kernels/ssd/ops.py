"""The SSD's intra-chunk term with its custom VJP, in the model's layout.

``ssd_intra(C, B, cs, x, dt)`` takes the arguments of the XLA form
(``models/mamba2.py`` ``_intra_chunk``), which is its reference: C, B
[b, c, L, G, N] and x [b, c, L, G, J, P] in the mixer's dtype, cs and Δ
[b, c, L, G, J] float32. It returns y [b, c, L, G, J, P] in float32. The
residuals are the inputs; the kernels form Δx in VMEM, and the backward
kernel recomputes the masks.
``interpret=True`` runs the Pallas interpreter (CPU validation); the
default compiles the Mosaic kernels for the TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import HEADS, TILE, ssd_intra_bwd, ssd_intra_fwd


def supported(L: int, H: int, P: int, N: int, G: int) -> bool:
    """Whether the kernels tile a chunk of L steps, H heads of P, a state
    of N and G groups: whole 128-step tiles, whole blocks of ``HEADS``
    heads in each group."""
    return L % TILE == 0 and P in (64, 128) and N in (128, 256) and (H // G) % HEADS == 0


def _time_minor(a, *rest):
    """[b, c, L, ...] -> [b, *rest, c·L]: the steps on the minor axis, the
    other axes regrouped as ``rest``."""
    b, nc, L = a.shape[:3]
    return jnp.moveaxis(a.reshape(b, nc * L, *a.shape[3:]), 1, -1).reshape(b, *rest, nc * L)


def _model_layout(a, shape):
    """The inverse of ``_time_minor``: [b, ..., c·L] -> ``shape``, [b, c, L, ...]."""
    b, nc, L = shape[:3]
    return jnp.moveaxis(a.reshape(b, *shape[3:], nc * L), -1, 1).reshape(shape)


def _layouts(C, B, cs, x, dt):
    b, nc, L, G, J, P = x.shape
    GN = G * C.shape[-1]
    args = (_time_minor(C, GN), _time_minor(B, GN),
            jnp.swapaxes(cs.reshape(b, nc, L, G * J), 2, 3),          # [b, c, H, L]
            _time_minor(x, G * J, P), _time_minor(dt, G * J))
    return args, dict(L=L, J=J)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_intra(C, B, cs, x, dt, interpret: bool = False):
    args, dims = _layouts(C, B, cs, x, dt)
    return _model_layout(ssd_intra_fwd(*args, **dims, interpret=interpret), x.shape)


def _fwd(C, B, cs, x, dt, interpret):
    return ssd_intra(C, B, cs, x, dt, interpret), (C, B, cs, x, dt)


def _bwd(interpret, res, dy):
    C, B, cs, x, dt = res
    args, dims = _layouts(C, B, cs, x, dt)
    dC, dB, dcs, dx, ddt = ssd_intra_bwd(*args, _time_minor(dy, *args[3].shape[1:3]), **dims,
                                         interpret=interpret)
    return (_model_layout(dC, C.shape), _model_layout(dB, B.shape),
            jnp.swapaxes(dcs, 2, 3).reshape(cs.shape), _model_layout(dx, x.shape),
            _model_layout(ddt, dt.shape))


ssd_intra.defvjp(_fwd, _bwd)
