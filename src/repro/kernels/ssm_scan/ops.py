"""jit'd wrapper for the selective-scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import ssm_scan_kernel


@partial(jax.jit, static_argnames=("chunk", "dblk", "interpret"))
def ssm_scan(dt, x, Bm, Cm, A, D, *, chunk: int = 64, dblk: int = 256,
             interpret: bool = False):
    return ssm_scan_kernel(
        dt, x, Bm, Cm, A, D, chunk=chunk, dblk=dblk, interpret=interpret
    )
