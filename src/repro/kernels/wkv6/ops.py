"""jit'd wrapper for the WKV6 kernel (model layout [B, T, H, N])."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import wkv6_kernel


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w_log, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w_log: [B, T, H, N]; u: [H, N] -> [B, T, H, N] fp32."""
    out = wkv6_kernel(
        r.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        w_log.transpose(0, 2, 1, 3),
        u,
        chunk=chunk,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
