"""GPipe-style pipeline parallelism over a mesh axis (shard_map +
collective_permute).

Multi-pod topology makes the 'pod' axis the natural pipeline dimension:
inter-pod (DCI) bandwidth is far below in-pod ICI, and pipelining moves
only layer activations across pods once per microbatch instead of
all-reducing every gradient. Stages hold contiguous period-groups of the
layer stack; the schedule is the classic GPipe fill-drain loop expressed
as a ``lax.scan`` over (microbatches + stages - 1) ticks with a
``collective_permute`` shifting activations to the next stage each tick.

This module is self-contained and validated on a host-device mesh in
``tests/test_pipeline.py``; production launchers opt in with
``--pipeline pod``. (The dry-run default keeps pod as a pure DP axis —
see DESIGN.md §5.)
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable,        # (stage_params, x [Bm, ...]) -> y
    params_stacked,            # pytree stacked over stages on axis 0
    x_microbatches: jax.Array, # [n_micro, Bm, ...] (already on stage 0)
    mesh: Mesh,
    axis: str = "pod",
):
    """Run the pipeline forward. Returns final-stage outputs
    [n_micro, Bm, ...]. Correctness oracle: applying the stages
    sequentially on one device."""
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    ticks = n_micro + n_stages - 1

    def per_stage(params, xs):
        # P(axis) leaves a local leading stage dim of 1: drop it
        params = jax.tree.map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(axis)

        def tick(carry, t):
            outputs, inflight = carry
            # which microbatch enters stage 0 at tick t
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            stage0_in = jax.lax.dynamic_index_in_dim(
                xs, mb_idx, axis=0, keepdims=False
            )
            x_in = jnp.where(stage_id == 0, stage0_in, inflight)
            active = (t - stage_id >= 0) & (t - stage_id < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # shift to the next stage (ring; last stage's output falls off)
            shifted = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # last stage writes its finished microbatch
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_done = (stage_id == n_stages - 1) & (t >= n_stages - 1)
            outputs = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, done_idx, axis=0
                ),
                lambda o: o,
                outputs,
            )
            return (outputs, shifted), None

        outputs0 = jnp.zeros_like(xs)
        inflight0 = jnp.zeros_like(xs[0])
        (outputs, _), _ = jax.lax.scan(
            tick, (outputs0, inflight0), jnp.arange(ticks)
        )
        # broadcast final outputs from the last stage to all stages
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    in_specs = (P(axis), P())        # params sharded by stage; x replicated
    out_specs = P()
    fn = jax.shard_map(
        per_stage, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(params_stacked, x_microbatches)


def split_microbatches(x: jax.Array, n_micro: int) -> jax.Array:
    B = x.shape[0]
    assert B % n_micro == 0
    return x.reshape(n_micro, B // n_micro, *x.shape[1:])
