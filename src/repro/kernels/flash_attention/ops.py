"""jit'd public wrapper for the flash-attention kernel.

Model code keeps [B, S, H, Dh] layout; the kernel wants [B, H, S, Dh].
``interpret=True`` runs the Pallas interpreter (CPU validation); the
default compiles the Mosaic kernel for the TPU.
"""

from __future__ import annotations

from functools import partial

import jax

from .kernel import flash_attention_kernel


@partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,          # [B, Sq, H, Dh]  (model layout)
    k: jax.Array,          # [B, Sk, Hk, Dh]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    out = flash_attention_kernel(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
