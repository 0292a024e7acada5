"""``LM.loss`` against a plain cross-entropy over ``LM.logits``.

``LM.loss`` runs over the sequence in chunks and forms its gradient inside
the forward chunk loop (a ``custom_vjp``); the reference here is the
unchunked loss that ``jax.value_and_grad`` differentiates the usual way.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import reduced
from repro.configs.base import ModelConfig, get_config
from repro.models.transformer import LM
from repro.training.optimizer import AdamWConfig, OptimizerConfig, Schedule
from repro.training.train_step import TrainConfig, make_train_step

D = 32


def _cfg(vocab: int, tied: bool, scaling) -> ModelConfig:
    return ModelConfig(name="loss-test", family="dense", n_layers=1, d_model=D, d_ff=64,
                       vocab_size=vocab, tie_embeddings=tied, logits_scaling=scaling)


def _plain_loss(p, cfg, hidden, labels, mask):
    logits = LM.logits(p, cfg, hidden)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


CASES = {
    # name: (vocab, tied, logits_scaling, seq, masked, dtype)
    "tied": (256, True, None, 64, False, jnp.float32),
    "untied_head": (256, False, None, 64, False, jnp.float32),
    "scaling8": (256, True, 8.0, 64, False, jnp.float32),
    "padded_vocab_untied": (250, False, None, 64, False, jnp.float32),
    "padded_vocab_tied": (250, True, 8.0, 64, False, jnp.float32),
    "masked": (256, True, None, 64, True, jnp.float32),
    "ragged_seq": (256, False, 8.0, 50, True, jnp.float32),
    "bf16": (256, True, 8.0, 64, True, jnp.bfloat16),
    "bf16_all": (250, False, 8.0, 50, True, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_loss_and_grads_match_plain_cross_entropy(case):
    vocab, tied, scaling, S, masked, dtype = CASES[case]
    cfg = _cfg(vocab, tied, scaling)
    B, Vp = 2, cfg.vocab_padded
    kt, kh, kl, km = jax.random.split(jax.random.PRNGKey(3), 4)
    shape = (Vp, D) if tied else (D, Vp)
    table = (jax.random.normal(kt, shape) * 0.5).astype(dtype)
    p = {"embed" if tied else "lm_head": table}
    hidden = jax.random.normal(kh, (B, S, D)).astype(dtype)
    labels = jax.random.randint(kl, (B, S), 0, vocab)
    mask = ((jax.random.uniform(km, (B, S)) > 0.3).astype(jnp.float32) if masked
            else jnp.ones((B, S), jnp.float32))

    def chunked(p, h):
        return LM.loss(p, cfg, h, labels, mask if masked else None, seq_chunk=16)

    def plain(p, h):
        return _plain_loss(p, cfg, h, labels, mask)

    got, (gp, gh) = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(p, hidden)
    want, (wp, wh) = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(p, hidden)
    (name,) = p
    assert gh.dtype == hidden.dtype and gp[name].dtype == table.dtype
    # bf16 operands round dlogits and the table's carry to bf16 at each chunk
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for g, w in ((gh, wh), (gp[name], wp[name])):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) <= rtol * np.linalg.norm(w), case
    if masked:
        # masked tokens get no gradient
        assert not np.any(np.asarray(gh, np.float32)[np.asarray(mask) == 0])


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def test_grad_of_loss_runs_three_vocab_products_a_chunk():
    """The gradient holds three products with the vocabulary a chunk (the
    logits, dh and dtable), all in the forward chunk loop: the logits are
    not computed a second time for the backward pass."""
    cfg = _cfg(384, True, 8.0)
    Vp = cfg.vocab_padded
    p = {"embed": jnp.zeros((Vp, D), jnp.bfloat16)}
    hidden = jnp.zeros((2, 64, D), jnp.bfloat16)
    labels = jnp.zeros((2, 64), jnp.int32)

    def loss(p, h):
        return LM.loss(p, cfg, h, labels, seq_chunk=16)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(p, hidden).jaxpr

    def vocab_dots(jaxpr, in_loop):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and in_loop:
                shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
                n += any(Vp in s for s in shapes)
            for sub in _subjaxprs(eqn):
                n += vocab_dots(sub, in_loop or eqn.primitive.name == "scan")
        return n

    assert vocab_dots(jaxpr, False) == 3


def test_train_step_with_microbatches_matches_plain_cross_entropy():
    """Through ``make_train_step`` with two microbatches, the step's loss and
    gradient (AdamW's first moment after one step, unclipped) equal those of
    the plain unchunked loss."""
    cfg = dataclasses.replace(reduced(get_config("granite-4.0-h-micro")),
                              param_dtype="float32", compute_dtype="float32")
    adamw = AdamWConfig(max_grad_norm=1e9, schedule=Schedule(base_lr=1e-3, warmup_steps=1,
                                                             decay_steps=10))
    tcfg = TrainConfig(optimizer=OptimizerConfig(kind="adamw", adamw=adamw),
                       microbatches=2, seq_chunk_loss=16)
    init_state, train_step, _ = make_train_step(cfg, tcfg)
    state = init_state(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 40), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    params = state["params"]
    new_state, metrics = jax.jit(train_step)(state, batch)

    def plain(params, mb):
        hidden, aux = LM.apply(params, cfg, mb["tokens"])
        mask = jnp.ones(mb["labels"].shape, jnp.float32)
        loss = _plain_loss(params, cfg, hidden, mb["labels"], mask)
        return loss + tcfg.aux_loss_weight * aux, loss

    grad = jax.jit(jax.value_and_grad(plain, has_aux=True))
    halves = [jax.tree.map(lambda x, i=i: x[2 * i:2 * i + 2], batch) for i in range(2)]
    outs = [grad(params, mb) for mb in halves]
    want_loss = sum(float(o[0][1]) for o in outs) / 2
    want = jax.tree.map(lambda a, b: (a + b) / 2, outs[0][1], outs[1][1])
    np.testing.assert_allclose(float(metrics["loss"]), want_loss, rtol=1e-5)
    got = jax.tree.map(lambda m: m / (1 - adamw.b1), new_state["opt"]["m"])
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want), strict=True):
        g, w = np.asarray(g), np.asarray(w)
        scale = max(np.linalg.norm(w), 1e-6)
        assert np.linalg.norm(g - w) <= 1e-4 * scale, jax.tree_util.keystr(path)
