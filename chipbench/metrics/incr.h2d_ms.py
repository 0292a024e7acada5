"""Milliseconds per block-iteration from ``jax.device_put`` through
``block_until_ready``: the ``incr.h2d`` spans' mean."""


def read(rec):
    return rec.mean_ms("incr.h2d")
