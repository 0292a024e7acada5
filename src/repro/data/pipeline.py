"""Input pipeline on top of Sea: sharded token datasets with tiered
prefetch, consumed-shard eviction, and straggler-tolerant work stealing.

Dataset layout (all paths under the Sea mountpoint, physically on the
persistent tier until prefetched):

    dataset/<name>/meta.json
    dataset/<name>/shard_00000.npy     int32 [tokens_per_shard]

The pipeline stages upcoming shards into the fast tier (Sea prefetch),
yields fixed-shape [B, S] batches double-buffered on the host, and drops
cache copies once consumed (the in-memory-computing pattern: inputs are
re-readable from the persistent tier, so cache space is better spent on
the shards ahead).

Work stealing: shards live in a global deque; each worker claims the next
shard when idle. A straggler's unprocessed claims return to the queue
when the StragglerDetector flags it (launcher side), so slow nodes cost
their own throughput only.
"""

from __future__ import annotations

import json
import os
import queue
import threading
from collections import deque
from dataclasses import dataclass

import numpy as np

from repro.core import Sea


# ------------------------------------------------------------------ build
def write_dataset(
    sea: Sea,
    name: str,
    *,
    n_shards: int,
    tokens_per_shard: int,
    vocab_size: int,
    seed: int = 0,
) -> str:
    """Synthetic corpus: Zipfian tokens with local correlations (enough
    structure for a CE-loss to visibly decrease)."""
    rng = np.random.default_rng(seed)
    root = os.path.join(sea.fs.mount, "dataset", name)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    probs = 1.0 / ranks
    probs /= probs.sum()
    for i in range(n_shards):
        toks = rng.choice(vocab_size, size=tokens_per_shard, p=probs).astype(
            np.int32
        )
        # inject learnable bigram structure: every odd position repeats the
        # previous token with p=0.5
        repeat = rng.random(tokens_per_shard) < 0.5
        toks[1::2] = np.where(repeat[1::2], toks[0::2], toks[1::2])
        shard_path = os.path.join(root, f"shard_{i:05d}.npy")
        with sea.fs.open(shard_path, "wb") as f:
            np.save(f, toks, allow_pickle=False)
        sea.fs.persist(shard_path)   # inputs must survive cache eviction
    with sea.fs.open(os.path.join(root, "meta.json"), "w") as f:
        json.dump(
            {
                "n_shards": n_shards,
                "tokens_per_shard": tokens_per_shard,
                "vocab_size": vocab_size,
            },
            f,
        )
    sea.fs.persist(os.path.join(root, "meta.json"))
    return root


# ------------------------------------------------------------------ pipeline
@dataclass
class PipelineStats:
    shards_consumed: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    evictions: int = 0


class DataPipeline:
    """Iterator of {tokens, labels} numpy batches with Sea-tiered staging."""

    def __init__(
        self,
        sea: Sea,
        name: str,
        *,
        batch_size: int,
        seq_len: int,
        prefetch_shards: int = 2,
        evict_consumed: bool = True,
        start_shard: int = 0,
        worker_id: int = 0,
        n_workers: int = 1,
    ):
        self.sea = sea
        self.fs = sea.fs
        self.root = os.path.join(sea.fs.mount, "dataset", name)
        with self.fs.open(os.path.join(self.root, "meta.json")) as f:
            self.meta = json.load(f)
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.evict_consumed = evict_consumed
        self.stats = PipelineStats()
        # work-stealing queue of shard indices (strided start for locality)
        ids = list(range(start_shard, self.meta["n_shards"]))
        self._queue: "queue.Queue[int]" = queue.Queue()
        for sid in ids[worker_id::n_workers]:
            self._queue.put(sid)
        self._staged: "queue.Queue[tuple[int, np.ndarray]]" = queue.Queue(
            maxsize=prefetch_shards
        )
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._stage_loop, name="sea-data-prefetch", daemon=True
        )
        self._thread.start()

    # -- staging thread: PFS -> cache tier -> host memory --------------------
    def _shard_path(self, sid: int) -> str:
        return os.path.join(self.root, f"shard_{sid:05d}.npy")

    def _stage_loop(self) -> None:
        while not self._stop.is_set():
            try:
                sid = self._queue.get_nowait()
            except queue.Empty:
                self._staged.put((-1, None))
                return
            try:
                self._stage_one(sid)
            except Exception as e:  # surface failures to the consumer
                self._staged.put((-2, e))
                return

    def _stage_one(self, sid: int) -> None:
        path = self._shard_path(sid)
        key = self.fs.key_of(path)
        where = self.fs.where(path)
        if where is not None and where != self.fs.hierarchy.base.name:
            self.stats.cache_hits += 1
        elif not getattr(self.fs.config, "readahead", False):
            self.stats.cache_misses += 1
            # stage through the shared engine-backed primitive (same code
            # path as Flusher.prefetch): key-locked against racing
            # _evict/flusher moves, ledger admission before bytes move,
            # staging tmp cleaned up on failure. Best-effort — on any
            # transfer error the shard is read from its persistent copy.
            self.fs.stage_to_cache(key)
        else:
            # with predictive readahead enabled the bespoke staging is
            # redundant: the predictor observes the sequential shard
            # opens below and stages upcoming shards through the same
            # engine — with adaptive depth, cancellation, and waste
            # accounting this loop never had
            self.stats.cache_misses += 1
        with self.fs.open(path, "rb") as f:
            arr = np.load(f, allow_pickle=False)
        self._staged.put((sid, arr))

    def _evict(self, sid: int) -> None:
        """Drop the cache copy of a consumed shard (persistent copy stays)."""
        key = self.fs.key_of(self._shard_path(sid))
        with self.fs.key_lock(key):
            if self.fs.hierarchy.base.locate(key) is None:
                return  # never orphan the only copy
            evicted = False
            for tier in self.fs.hierarchy.cache_tiers:
                real = tier.locate(key)
                if real is not None:
                    try:
                        os.remove(real)
                        root = tier.root_of(real)
                        if root is not None:
                            tier.note_removed(root, key)
                        self.stats.evictions += 1
                        self.fs.telemetry.record_evict(0)
                        evicted = True
                    except OSError:
                        pass
            if evicted:
                self.fs.resolver.invalidate(key)

    # -- iteration --------------------------------------------------------------
    def __iter__(self):
        """Fixed-shape batches assembled from a list of staged chunks
        with an offset cursor — O(batch) per batch. (The previous
        implementation re-concatenated the whole remaining buffer on
        every shard arrival: O(total²) bytes copied over an epoch.)"""
        need = self.batch_size * (self.seq_len + 1)
        chunks: deque = deque()  # staged shard arrays, consumed in order
        offset = 0  # consumed prefix of chunks[0]
        have = 0  # unconsumed tokens across all chunks
        while True:
            while have < need:
                if self._stop.is_set():
                    # closed: the staging thread is (being) joined and
                    # may never post another item — a blocking get here
                    # would hang forever
                    return
                sid, arr = self._staged.get()
                if sid == -2:
                    raise RuntimeError("data staging failed") from arr
                if arr is None:
                    return  # staging exhausted; tail < one batch is dropped
                if arr.size:
                    chunks.append(arr)
                    have += arr.size
                self.stats.shards_consumed += 1
                if self.evict_consumed:
                    self._evict(sid)
            parts = []
            got = 0
            while got < need:
                head = chunks[0]
                take = min(head.size - offset, need - got)
                parts.append(head[offset : offset + take])
                got += take
                offset += take
                if offset == head.size:
                    chunks.popleft()
                    offset = 0
            have -= need
            chunk = np.concatenate(parts) if len(parts) > 1 else parts[0]
            chunk = chunk.reshape(self.batch_size, self.seq_len + 1)
            yield {
                "tokens": chunk[:, :-1].copy(),
                "labels": chunk[:, 1:].copy(),
            }

    # -- device feed ------------------------------------------------------------
    def device_iter(self, *, depth: int | None = None, put_fn=None):
        """Batches already on device: a feeder thread runs ``put_fn``
        (default ``jax.device_put`` per array) on batch N+1..N+depth
        while the consumer computes on batch N, so the host->device copy
        — the last hop of the base->cache->host->device pipeline — is
        double-buffered behind compute exactly like the staging thread
        double-buffers the base->cache->host hops. ``depth`` defaults to
        the ``device_prefetch`` config knob. A consumer that finds the
        buffer empty records a ``device_feed_stalls`` telemetry tick and
        its wait as a ``feed.wait`` span; each put is a ``feed.put`` span."""
        if depth is None:
            depth = max(1, getattr(self.fs.config, "device_prefetch", 2))
        if put_fn is None:
            import jax

            def put_fn(batch):
                return {k: jax.device_put(v) for k, v in batch.items()}

        fed: "queue.Queue" = queue.Queue(maxsize=depth)
        done = threading.Event()
        telemetry = self.fs.telemetry

        def _feed() -> None:
            try:
                for batch in self:
                    with telemetry.span("feed.put"):
                        item = (0, put_fn(batch))
                    while True:
                        if done.is_set():
                            return  # consumer gone: nobody reads a sentinel
                        if self._stop.is_set():
                            self._put_sentinel(fed, (-1, None), done)
                            return
                        try:
                            fed.put(item, timeout=0.05)
                            break
                        except queue.Full:
                            continue
                self._put_sentinel(fed, (-1, None), done)
            except BaseException as e:
                self._put_sentinel(fed, (-2, e), done)

        t = threading.Thread(
            target=_feed, name="sea-device-feed", daemon=True
        )
        t.start()
        try:
            while True:
                try:
                    tag, item = fed.get_nowait()
                except queue.Empty:
                    if done.is_set():
                        return
                    telemetry.record_device_feed_stall()
                    with telemetry.span("feed.wait"):
                        tag, item = fed.get()
                if tag == -2:
                    raise RuntimeError("device feed failed") from item
                if tag == -1:
                    return
                yield item
        finally:
            # stop + JOIN the feeder (it may be blocked in put): mirror
            # of close() for the device stage
            done.set()
            while t.is_alive():
                try:
                    while True:
                        fed.get_nowait()
                except queue.Empty:
                    pass
                t.join(timeout=0.05)

    @staticmethod
    def _put_sentinel(q: "queue.Queue", item, done: threading.Event) -> None:
        while not done.is_set():
            try:
                q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    # -- lifecycle --------------------------------------------------------------
    def __enter__(self) -> "DataPipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def close(self) -> None:
        """Stop and JOIN the staging thread (it may be blocked putting
        into the bounded staged queue: drain until it exits, so no
        daemon thread keeps reading shards after close returns)."""
        self._stop.set()
        while self._thread.is_alive():
            try:
                while True:
                    self._staged.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        # a consumer that raced the drain may already sit in a blocking
        # get(): hand it the end-of-data sentinel its __iter__ expects
        try:
            self._staged.put_nowait((-1, None))
        except queue.Full:
            pass
