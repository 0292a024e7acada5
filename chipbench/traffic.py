"""Inputs drawn from the seed: the training corpus and the incrementation
blocks. The same seed gives the same inputs; the references draw theirs
from here too, never from what the program wrote.

The corpus generator is a copy of ``repro.data.pipeline.write_dataset``'s
(Zipfian tokens, every odd position repeating the previous token with
probability 1/2), written in the program's dataset layout so that
``DataPipeline`` reads it.
"""

from __future__ import annotations

import json
import os

import numpy as np


def corpus_shards(seed: int, *, n_shards: int, tokens_per_shard: int, vocab_size: int):
    """Yield the corpus's shards, int32, in order."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64)
    probs /= probs.sum()
    for _ in range(n_shards):
        toks = rng.choice(vocab_size, size=tokens_per_shard, p=probs).astype(np.int32)
        repeat = rng.random(tokens_per_shard) < 0.5
        toks[1::2] = np.where(repeat[1::2], toks[0::2], toks[1::2])
        yield toks


def write_corpus(sea, name: str, seed: int, *, n_shards: int, tokens_per_shard: int,
                 vocab_size: int) -> None:
    """The corpus through Sea: ``dataset/<name>/shard_NNNNN.npy`` and
    ``meta.json``, each persisted to the base tier."""
    root = os.path.join(sea.fs.mount, "dataset", name)
    shards = corpus_shards(seed, n_shards=n_shards, tokens_per_shard=tokens_per_shard,
                           vocab_size=vocab_size)
    for i, toks in enumerate(shards):
        path = os.path.join(root, f"shard_{i:05d}.npy")
        with sea.fs.open(path, "wb") as f:
            np.save(f, toks, allow_pickle=False)
        sea.fs.persist(path)
    meta = os.path.join(root, "meta.json")
    with sea.fs.open(meta, "w") as f:
        json.dump({"n_shards": n_shards, "tokens_per_shard": tokens_per_shard,
                   "vocab_size": vocab_size}, f)
    sea.fs.persist(meta)


def first_batches(seed: int, *, n: int, batch: int, seq: int, tokens_per_shard: int,
                  vocab_size: int) -> list[dict]:
    """The first ``n`` training batches as ``DataPipeline`` cuts them:
    consecutive rows of ``seq + 1`` tokens, inputs and next-token labels."""
    need = n * batch * (seq + 1)
    shards = corpus_shards(seed, n_shards=-(-need // tokens_per_shard),
                           tokens_per_shard=tokens_per_shard, vocab_size=vocab_size)
    flat = np.concatenate(list(shards))[:need].reshape(n, batch, seq + 1)
    return [{"tokens": b[:, :-1].copy(), "labels": b[:, 1:].copy()} for b in flat]


def incr_input(seed: int, k: int, n_elems: int) -> np.ndarray:
    """Input block ``k``: float32 holding uniform 16-bit intensities."""
    rng = np.random.default_rng([seed, k])
    raw = np.frombuffer(rng.bytes(2 * n_elems), dtype=np.uint16)
    return raw.astype(np.float32)
