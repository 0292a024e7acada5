"""Compiles for a described TPU v5e, with no chip attached: the training
step at granite-3-2b's published widths, the Pallas kernels with their
default ``interpret=False`` and the Mamba-2 mixer's gradient at
granite-4.0-h-micro's. What the chip's compiler would refuse,
and a step that would not fit one chip's HBM, fail here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# HBM the compiler lets one program use on a 16 GB v5e
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a chip that is not attached is written to the cache but
    cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_train_step_fits_one_v5e_at_granite_width(one_chip):
    from repro.launch.train import build_model_config, parse_args, train_config
    from repro.training.train_step import make_train_step

    batch, seq = 4, 1024
    args = parse_args(["--arch", "granite-3-2b", "--n-layers", "2",
                       "--batch", str(batch), "--seq", str(seq)])
    cfg = build_model_config(args)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers) == (2048, 8192, 49155, 2)
    a = cfg.attention
    assert (a.num_heads, a.num_kv_heads, a.head_dim) == (32, 8, 64)

    init_state, train_step, _ = make_train_step(cfg, train_config(args, cfg))
    template = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = jax.tree.map(lambda x: _on(one_chip, x.shape, x.dtype), template)
    tokens = _on(one_chip, (batch, seq), jnp.int32)
    compiled = (
        jax.jit(train_step, donate_argnums=0)
        .lower(state, {"tokens": tokens, "labels": tokens})
        .compile()
    )
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES
    # attention runs as the fused kernel: no f32 score tile reaches HBM
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(r"f32\[[0-9,]*512,1024\]", hlo)


def _flash(s):
    from repro.kernels.flash_attention.ops import flash_attention

    bf = jnp.bfloat16
    return flash_attention, (_on(s, (4, 1024, 32, 64), bf), _on(s, (4, 1024, 8, 64), bf),
                             _on(s, (4, 1024, 8, 64), bf))


def _rmsnorm(s):
    from repro.kernels.rmsnorm.ops import rmsnorm

    return rmsnorm, (_on(s, (4096, 2048), jnp.bfloat16), _on(s, (2048,), jnp.bfloat16))


def _ssm_scan(s):
    from repro.kernels.ssm_scan.ops import ssm_scan

    B, T, d_in, N = 2, 1024, 8192, 16   # jamba-v0.1: d_model 4096, expand 2
    return ssm_scan, (_on(s, (B, T, d_in)), _on(s, (B, T, d_in)), _on(s, (B, T, N)),
                      _on(s, (B, T, N)), _on(s, (d_in, N)), _on(s, (d_in,)))


def _wkv6(s):
    from repro.kernels.wkv6.ops import wkv6

    B, T, H, N = 2, 1024, 64, 64        # rwkv6-7b: d_model 4096, head size 64
    bf = jnp.bfloat16
    return wkv6, (_on(s, (B, T, H, N), bf), _on(s, (B, T, H, N), bf),
                  _on(s, (B, T, H, N), bf), _on(s, (B, T, H, N)), _on(s, (H, N)))


def _ssd(s):
    from repro.kernels.ssd.ops import ssd_intra

    # granite-4.0-h-micro at 8k: 64 heads of 64, state 128, one group, chunk 256
    b, nc, L, G, J, P, N = 2, 32, 256, 1, 64, 64, 128
    bf = jnp.bfloat16
    return ssd_intra, (_on(s, (b, nc, L, G, N), bf), _on(s, (b, nc, L, G, N), bf),
                       _on(s, (b, nc, L, G, J)), _on(s, (b, nc, L, G, J, P), bf),
                       _on(s, (b, nc, L, G, J)))


@pytest.mark.parametrize("build", [_flash, _rmsnorm, _ssm_scan, _wkv6, _ssd],
                         ids=["flash_attention", "rmsnorm", "ssm_scan", "wkv6", "ssd"])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    # the Mosaic kernel itself, not the interpreter's XLA loop
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES


def test_mamba2_grad_runs_ssd_kernel_for_v5e(one_chip):
    """The gradient of a rematerialised Mamba-2 mixer at granite-4.0-h-micro's
    widths, batch 2 x 8192: the intra-chunk term runs as the SSD kernels,
    forward (twice, with the recompute) and backward, and no L x L decay
    mask of float32 is left in the compiled program."""
    from repro.configs.base import get_config
    from repro.models import mamba2
    from repro.models.layers import Layout

    cfg = get_config("granite-4.0-h-micro")
    m, d = cfg.mamba2, cfg.d_model
    assert (m.n_heads, m.head_dim, m.d_state, m.n_groups, m.chunk, d) == (64, 64, 128, 1, 256, 2048)
    layout = Layout(jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16))
    params = jax.eval_shape(lambda k: mamba2.mamba2_init(k, m, d, layout)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), params)
    x = _on(one_chip, (2, 8192, d), jnp.bfloat16)

    def loss(p, x):
        mixer = jax.checkpoint(lambda p, x: mamba2.mamba2_apply(p, m, x, eps=cfg.norm_eps))
        return jnp.sum(mixer(p, x).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss)).lower(params, x).compile()
    hlo = compiled.as_text()
    assert re.search(r"ssd_intra_fwd[.\w]* = ", hlo) and re.search(r"ssd_intra_bwd[.\w]* = ", hlo)
    assert not re.search(r"f32\[[0-9,]*256,256\]", hlo)
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES
