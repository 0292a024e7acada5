"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests on invariants."""

import collections
import contextlib
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd.ops import ssd_intra
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ------------------------------------------------------------------ flash
FLASH_SWEEP = [
    # (B, H, Hk, Sq, Sk, Dh, causal, window, dtype)
    (1, 4, 4, 128, 128, 64, True, None, jnp.float32),
    (2, 8, 2, 256, 256, 64, True, None, jnp.float32),      # GQA 4:1
    (1, 4, 1, 128, 128, 128, True, None, jnp.float32),     # MQA
    (1, 4, 4, 200, 200, 64, True, None, jnp.float32),      # ragged/padded
    (1, 4, 2, 256, 256, 64, True, 64, jnp.float32),        # sliding window
    (1, 4, 4, 128, 128, 64, False, None, jnp.float32),     # bidirectional
    (2, 4, 2, 256, 256, 64, True, None, jnp.bfloat16),
    (1, 8, 8, 512, 512, 96, True, None, jnp.bfloat16),     # phi3 head_dim
]


@pytest.mark.parametrize(
    "B,H,Hk,Sq,Sk,Dh,causal,window,dtype", FLASH_SWEEP
)
def test_flash_attention_matches_ref(B, H, Hk, Sq, Sk, Dh, causal, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(k1, (B, Sq, H, Dh), dtype)
    k = rand(k2, (B, Sk, Hk, Dh), dtype)
    v = rand(k3, (B, Sk, Hk, Dh), dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=64, block_k=64, interpret=True,
    )
    ref = attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
    ).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_causality_property():
    """Perturbing future tokens never changes past outputs."""
    key = jax.random.PRNGKey(1)
    B, H, S, Dh = 1, 2, 128, 64
    q = rand(key, (B, S, H, Dh))
    k = rand(jax.random.fold_in(key, 1), (B, S, H, Dh))
    v = rand(jax.random.fold_in(key, 2), (B, S, H, Dh))
    out1 = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                           interpret=True)
    k2 = k.at[:, 100:].set(99.0)
    v2 = v.at[:, 100:].set(-99.0)
    out2 = flash_attention(q, k2, v2, causal=True, block_q=32, block_k=32,
                           interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, :100]), np.asarray(out2[:, :100]), rtol=1e-5, atol=1e-5
    )


@settings(max_examples=10, deadline=None)
@given(
    sq=st.integers(16, 160),
    hk=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    blk=st.sampled_from([32, 64]),
)
def test_flash_attention_block_invariance(sq, hk, g, blk):
    """Output is independent of the block decomposition."""
    key = jax.random.PRNGKey(sq)
    B, Dh = 1, 64
    H = hk * g
    q = rand(key, (B, sq, H, Dh))
    k = rand(jax.random.fold_in(key, 1), (B, sq, hk, Dh))
    v = rand(jax.random.fold_in(key, 2), (B, sq, hk, Dh))
    a = flash_attention(q, k, v, block_q=blk, block_k=blk, interpret=True)
    b = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------------ splash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("H,Hk", [(4, 1), (2, 2)], ids=["gqa4", "mha"])
@pytest.mark.parametrize("Dh", [64, 128])
def test_fused_causal_attention_matches_chunked(Dh, H, Hk, dtype):
    """The TPU kernel path (interpreted) matches ``chunked_attention`` in
    the output and in dq/dk/dv."""
    from repro.models.attention import chunked_attention, fused_causal_attention

    B, S = 2, 256
    ks = jax.random.split(jax.random.PRNGKey(Dh + H), 4)
    q = rand(ks[0], (B, S, H, Dh), dtype)
    k = rand(ks[1], (B, S, Hk, Dh), dtype)
    v = rand(ks[2], (B, S, Hk, Dh), dtype)
    cot = rand(ks[3], (B, S, H, Dh), dtype)

    def out_and_grads(attend):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out, *vjp(cot))

    got = out_and_grads(partial(fused_causal_attention, interpret=True))
    want = out_and_grads(chunked_attention)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol, err_msg=name,
        )


def _granite_attention(**changes):
    from repro.configs.base import AttentionConfig

    return dataclasses.replace(
        AttentionConfig(num_heads=32, num_kv_heads=8, head_dim=64), **changes
    )


def _jax_mesh():
    return jax.sharding.use_abstract_mesh(
        jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    )


def _model_mesh():
    from repro.distributed.sharding import default_rules, logical_axis_rules

    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    return logical_axis_rules(mesh, default_rules(multi_pod=False))


SELECTION = [
    # (case, attention config changes, local layer, S, context, kernel expected)
    ("granite", {}, False, 256, contextlib.nullcontext, True),
    ("softcap", {"logit_softcap": 50.0}, False, 256, contextlib.nullcontext, False),
    ("window", {"sliding_window": 64}, True, 256, contextlib.nullcontext, False),
    ("bidirectional", {"causal": False}, False, 256, contextlib.nullcontext, False),
    ("ragged", {}, False, 200, contextlib.nullcontext, False),
    ("jax_mesh", {}, False, 256, _jax_mesh, False),
    ("model_mesh", {}, False, 256, _model_mesh, False),
]


@pytest.mark.parametrize(
    "changes,local,S,context,kernel",
    [c[1:] for c in SELECTION], ids=[c[0] for c in SELECTION],
)
def test_attention_selects_kernel_from_the_call(changes, local, S, context, kernel):
    """Lowered for TPU, only a plain causal, tileable, unsharded
    self-attention holds the splash kernel; lowered for CPU, none does."""
    from repro.models import attention as attn
    from repro.models.layers import Layout

    cfg = _granite_attention(**changes)
    D = 256
    params, _ = attn.attn_init(
        jax.random.PRNGKey(0), cfg, D, Layout(jnp.bfloat16, jnp.bfloat16), 1e-5
    )
    x = jax.ShapeDtypeStruct((1, S, D), jnp.bfloat16)

    def loss(p, x):
        out = attn.attn_apply(p, cfg, x, local=local, eps=1e-5)
        return jnp.sum(out.astype(jnp.float32))

    def lowered(platform):
        with context():
            traced = jax.jit(jax.grad(loss)).trace(params, x)
            return traced.lower(lowering_platforms=(platform,)).as_text()

    assert ("tpu_custom_call" in lowered("tpu")) == kernel
    assert "tpu_custom_call" not in lowered("cpu")


# ------------------------------------------------------------------ ssd
SSD_CASES = [
    # (case, chunk, T, heads, groups, head_dim, state, dtype, Δ·A scale, through ssd)
    ("c128_g1_f32", 128, 256, 8, 1, 64, 128, jnp.float32, 1.0, False),
    ("c256_g1_bf16", 256, 256, 8, 1, 64, 128, jnp.bfloat16, 1.0, False),
    ("c256_g2_f32", 256, 512, 16, 2, 64, 128, jnp.float32, 1.0, False),
    ("c128_g2_bf16", 128, 256, 16, 2, 64, 128, jnp.bfloat16, 1.0, False),
    ("c128_p128_n256_f32", 128, 256, 8, 1, 128, 256, jnp.float32, 1.0, False),
    ("underflow_f32", 256, 256, 8, 1, 64, 128, jnp.float32, 300.0, False),
    ("ragged_T_ssd_bf16", 128, 300, 8, 1, 64, 128, jnp.bfloat16, 1.0, True),
]


@pytest.mark.parametrize(
    "chunk,T,H,G,P,N,dtype,scale,through_ssd",
    [c[1:] for c in SSD_CASES], ids=[c[0] for c in SSD_CASES],
)
def test_ssd_intra_kernel_matches_xla(monkeypatch, chunk, T, H, G, P, N, dtype, scale,
                                      through_ssd):
    """The SSD's intra-chunk kernel pair (interpreted) matches the XLA form
    ``_intra_chunk`` in y and in dC, dB, dcs, dx and dΔ; through ``ssd``,
    in y, the final state and the gradients of x, Δ, B and C. ``scale``
    multiplies Δ·A: at 300 most of each chunk's mask underflows to 0."""
    from repro.models import mamba2

    b, J = 2, H // G
    ks = jax.random.split(jax.random.PRNGKey(chunk + T + H), 7)
    x = rand(ks[0], (b, T, H, P), dtype)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, T, H), minval=-7.0, maxval=-2.3))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0) * scale
    Bm = rand(ks[3], (b, T, G, N), dtype)
    Cm = rand(ks[4], (b, T, G, N), dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5

    if through_ssd:
        def run(intra):
            monkeypatch.setattr(mamba2, "_intra_chunk_dispatch", intra)
            (y, h), vjp = jax.vjp(lambda *a: mamba2.ssd(a[0], a[1], A, a[2], a[3], chunk),
                                  x, dt, Bm, Cm)
            cot = (rand(ks[5], y.shape), rand(ks[6], h.shape))
            return dict(zip(("y", "h", "dx", "ddt", "dB", "dC"), (y, h, *vjp(cot))))

        got = run(lambda *a: ssd_intra(*a, True))
        want = run(mamba2._intra_chunk)
    else:
        nc = T // chunk
        cs = jnp.cumsum((dt * A).reshape(b, nc, chunk, G, J), axis=2)
        args = (Cm.reshape(b, nc, chunk, G, N), Bm.reshape(b, nc, chunk, G, N), cs,
                x.reshape(b, nc, chunk, G, J, P), dt.reshape(b, nc, chunk, G, J))
        dy = rand(ks[5], (b, nc, chunk, G, J, P))

        def run(intra):
            y, vjp = jax.vjp(intra, *args)
            return dict(zip(("y", "dC", "dB", "dcs", "dx", "ddt"), (y, *vjp(dy))))

        got = run(lambda *a: ssd_intra(*a, True))
        want = run(mamba2._intra_chunk)
    for name, w in want.items():
        g = np.asarray(got[name], np.float32)
        w = np.asarray(w, np.float32)
        assert g.shape == w.shape and np.isfinite(g).all(), name
        np.testing.assert_allclose(g, w, rtol=0, atol=tol * float(np.max(np.abs(w))),
                                   err_msg=name)


SSD_SELECTION = [
    # (case, chunk, head_dim, context, kernel expected on TPU)
    ("granite", 256, 64, contextlib.nullcontext, True),
    ("chunk_64", 64, 64, contextlib.nullcontext, False),
    ("head_dim_32", 256, 32, contextlib.nullcontext, False),
    ("jax_mesh", 256, 64, lambda: _jax_mesh(), False),
    ("model_mesh", 256, 64, lambda: _model_mesh(), False),
]


@pytest.mark.parametrize("chunk,P,context,kernel", [c[1:] for c in SSD_SELECTION],
                         ids=[c[0] for c in SSD_SELECTION])
def test_ssd_selects_kernel_from_the_shape(chunk, P, context, kernel):
    """Lowered for TPU, only a tileable chunk and head size with no mesh
    bound hold the SSD kernels; lowered for CPU, none does, and ``ssd``
    equals itself run on the XLA form."""
    from repro.models import mamba2

    b, T, H, N = 1, 512, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = rand(ks[0], (b, T, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(rand(ks[1], (b, T, H)) - 4.0)
    A = -jnp.exp(rand(ks[2], (H,)))
    Bm = rand(ks[3], (b, T, 1, N), jnp.bfloat16)
    Cm = rand(ks[4], (b, T, 1, N), jnp.bfloat16)

    def loss(x, dt, Bm, Cm):
        y, h = mamba2.ssd(x, dt, A, Bm, Cm, chunk)
        return jnp.sum(y) + jnp.sum(h)

    def lowered(platform):
        with context():
            traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).trace(x, dt, Bm, Cm)
            return traced.lower(lowering_platforms=(platform,)).as_text()

    assert ("ssd_intra_bwd" in lowered("tpu")) == kernel
    assert "tpu_custom_call" not in lowered("cpu")
    got = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, Bm, Cm)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mamba2, "_intra_chunk_dispatch", mamba2._intra_chunk)
        want = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, Bm, Cm)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


def test_ssd_kernel_body_traces_once_per_shape(monkeypatch):
    """Four calls of the SSD kernels with one shape, lowered for TPU, trace
    each kernel body at most once (a model has a call site per layer)."""
    from repro.kernels.ssd import kernel as ssd_k

    traced = collections.Counter()
    for name in ("_fwd_kernel", "_bwd_kernel"):
        body = getattr(ssd_k, name)
        monkeypatch.setattr(ssd_k, name, lambda *a, _b=body, _n=name, **k: (
            traced.update([_n]), _b(*a, **k))[1])
    b, nc, L, G, J, P, N = 1, 2, 128, 1, 8, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    args = (rand(ks[0], (b, nc, L, G, N), jnp.bfloat16), rand(ks[1], (b, nc, L, G, N), jnp.bfloat16),
            jnp.cumsum(-jnp.abs(rand(ks[2], (b, nc, L, G, J))), axis=2),
            rand(ks[3], (b, nc, L, G, J, P), jnp.bfloat16), jnp.abs(rand(ks[4], (b, nc, L, G, J))))

    def loss(*a):
        return sum(jnp.sum(ssd_intra(*a) * (i + 1)) for i in range(4))

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "ssd_intra_fwd" in lowered and "ssd_intra_bwd" in lowered
    assert traced["_fwd_kernel"] <= 1 and traced["_bwd_kernel"] <= 1, traced


# ------------------------------------------------------------------ wkv6
WKV_SWEEP = [
    # (B, H, T, N, chunk, dtype)
    (1, 2, 64, 16, 16, jnp.float32),
    (2, 4, 128, 64, 64, jnp.float32),
    (1, 2, 128, 32, 32, jnp.bfloat16),
    (2, 1, 256, 64, 64, jnp.float32),
]


def wkv_inputs(B, H, T, N, dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    r = rand(ks[0], (B, T, H, N), dtype)
    k = rand(ks[1], (B, T, H, N), dtype)
    v = rand(ks[2], (B, T, H, N), dtype)
    # realistic decays: w_log = -exp(x) in [-6, 1] -> decay in (0, 1)
    w_log = -jnp.exp(
        jax.random.uniform(ks[3], (B, T, H, N), minval=-6.0, maxval=1.0)
    ).astype(jnp.float32)
    u = rand(ks[4], (H, N)) * 0.5
    return r, k, v, w_log, u


@pytest.mark.parametrize("B,H,T,N,chunk,dtype", WKV_SWEEP)
def test_wkv6_matches_ref(B, H, T, N, chunk, dtype):
    r, k, v, w_log, u = wkv_inputs(B, H, T, N, dtype)
    out = wkv6(r, k, v, w_log, u, chunk=chunk, interpret=True)
    ref = wkv6_ref(
        r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), w_log.transpose(0, 2, 1, 3), u,
    ).transpose(0, 2, 1, 3)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol, atol=tol)


def test_wkv6_chunk_invariance():
    """Chunk size must not change the result (state handoff correctness)."""
    r, k, v, w_log, u = wkv_inputs(1, 2, 128, 32, jnp.float32, key=3)
    a = wkv6(r, k, v, w_log, u, chunk=16, interpret=True)
    b = wkv6(r, k, v, w_log, u, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_wkv6_matches_model_path():
    """The XLA chunked implementation used by the model (rwkv.wkv6_chunked)
    agrees with the Pallas kernel — kernel and model can swap freely."""
    from repro.models.rwkv import wkv6_chunked

    r, k, v, w_log, u = wkv_inputs(1, 2, 128, 32, jnp.float32, key=5)
    a = wkv6(r, k, v, w_log, u, chunk=32, interpret=True)
    b = wkv6_chunked(r, k, v, w_log, u, chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(t=st.sampled_from([32, 64, 96]), n=st.sampled_from([16, 32]))
def test_wkv6_decay_forgetting_property(t, n):
    """With total decay -> -inf between two halves, the second half's output
    is independent of the first half (the state is fully forgotten)."""
    r, k, v, w_log, u = wkv_inputs(1, 1, t, n, jnp.float32, key=t * n)
    cut = t // 2
    w_hard = w_log.at[:, cut].set(-50.0)  # one step erases the state
    out_full = wkv6(r, k, v, w_hard, u, chunk=16, interpret=True)
    r2 = r.at[:, :cut].set(0.123)
    k2 = k.at[:, :cut].set(-0.5)
    out_mod = wkv6(r2, k2, v, w_hard, u, chunk=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_full[:, cut + 1:]), np.asarray(out_mod[:, cut + 1:]),
        rtol=1e-4, atol=1e-4,
    )


# ------------------------------------------------------------------ ssm
SSM_SWEEP = [
    # (B, T, d_in, N, chunk, dblk)
    (1, 64, 64, 8, 16, 32),
    (2, 128, 128, 16, 64, 64),
    (1, 256, 64, 16, 64, 64),
]


def ssm_inputs(B, T, d_in, N, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    dt = jax.nn.softplus(rand(ks[0], (B, T, d_in)))
    x = rand(ks[1], (B, T, d_in))
    Bm = rand(ks[2], (B, T, N))
    Cm = rand(ks[3], (B, T, N))
    A = -jnp.exp(rand(ks[4], (d_in, N)) * 0.5)
    D = rand(ks[5], (d_in,))
    return dt, x, Bm, Cm, A, D


@pytest.mark.parametrize("B,T,d_in,N,chunk,dblk", SSM_SWEEP)
def test_ssm_scan_matches_ref(B, T, d_in, N, chunk, dblk):
    args = ssm_inputs(B, T, d_in, N)
    out = ssm_scan(*args, chunk=chunk, dblk=dblk, interpret=True)
    ref = ssm_scan_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_chunk_invariance():
    args = ssm_inputs(1, 128, 64, 16, key=7)
    a = ssm_scan(*args, chunk=16, dblk=32, interpret=True)
    b = ssm_scan(*args, chunk=128, dblk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------ rmsnorm
@pytest.mark.parametrize("shape", [(4, 128), (2, 64, 256), (3, 5, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    key = jax.random.PRNGKey(0)
    x = rand(key, shape, dtype, scale=3.0)
    scale = rand(jax.random.fold_in(key, 1), shape[-1:]) + 1.0
    out = rmsnorm(x, scale, interpret=True, block_rows=8)
    ref = rmsnorm_ref(x, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 33),
    d=st.sampled_from([64, 128, 384]),
    amp=st.floats(0.5, 100.0),   # amp >> sqrt(eps): the invariant's domain
)
def test_rmsnorm_output_rms_is_scale_rms(rows, d, amp):
    """RMS of the output equals RMS of the scale vector (norm invariant),
    for inputs well above eps — catches accumulation/layout bugs."""
    key = jax.random.PRNGKey(rows * d)
    x = rand(key, (rows, d), scale=amp)
    scale = jnp.ones((d,))
    out = rmsnorm(x, scale, interpret=True, block_rows=8)
    rms = np.sqrt(np.mean(np.square(np.asarray(out)), axis=-1))
    np.testing.assert_allclose(rms, np.ones_like(rms), rtol=1e-3, atol=1e-3)
