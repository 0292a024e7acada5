"""jit'd wrapper for the fused RMSNorm kernel."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import rmsnorm_kernel


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    return rmsnorm_kernel(
        x, scale, eps=eps, block_rows=block_rows, interpret=interpret
    )
