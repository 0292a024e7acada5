"""Per-tier telemetry counters and spans.

Lightweight, thread-safe counters so benchmarks and the framework can see
where bytes actually went (tier hit ratios, flush/evict volumes). Purely
observational — placement never consults telemetry (Sea stays stateless).

Spans time the layers where the work happens (the ``SPANS`` registry):
off by default, where a span site costs one attribute check and reads no
clock; :meth:`Telemetry.trace_spans` turns them on. Each span then adds
its count, wall and thread-CPU nanoseconds and bytes to a per-thread,
lock-free block, and — where ``jax`` is already imported — is also a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it on the
device trace's clock. This module never imports ``jax`` itself.

Counters are **per-process**: with ``shared_ledger`` deployments every Sea
instance exports its snapshot to ``<base_root>/.sea_ledger/telemetry/`` at
shutdown, and :func:`aggregate_snapshots` / :func:`load_aggregate` merge
them into one node-wide view (the numbers the paper reports per node).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field


@dataclass
class TierCounters:
    bytes_written: int = 0
    bytes_read: int = 0
    files_written: int = 0
    files_read: int = 0


@dataclass
class TransferCounters:
    """One tier pair ("src->dst") of the transfer engine's data plane."""

    nbytes: int = 0
    files: int = 0
    seconds: float = 0.0
    retries: int = 0


class ThreadCounters:
    """Per-thread counter block for the open fast path and for spans:
    plain int increments with **no lock at all** (each block is written by
    exactly one thread; CPython attribute stores are GIL-atomic). ``snapshot``
    folds the live blocks in non-destructively — counters only grow, so
    summing base + per-thread values is always an under-by-at-most-one
    -in-flight-increment view and exact once threads quiesce. Blocks of
    dead threads are folded into the base counters and dropped, so
    thread churn cannot grow the registry without bound."""

    __slots__ = ("owner", "redirect_hits", "fastpath_opens", "io_read", "spans")

    def __init__(self):
        self.owner = threading.current_thread()
        self.redirect_hits = 0
        self.fastpath_opens = 0
        #: tier -> [bytes_read, files_read]
        self.io_read: dict[str, list] = {}
        #: span name -> [count, wall_ns, cpu_ns, bytes]
        self.spans: dict[str, list] = {}

    def record_read(self, tier: str, nbytes: int) -> None:
        c = self.io_read.get(tier)
        if c is None:
            c = self.io_read[tier] = [0, 0]
        c[0] += nbytes
        c[1] += 1

    def add_span(self, name: str, wall_ns: int, cpu_ns: int, nbytes: int) -> None:
        c = self.spans.get(name)
        if c is None:
            c = self.spans[name] = [0, 0, 0, 0]
        c[0] += 1
        c[1] += wall_ns
        c[2] += cpu_ns
        c[3] += nbytes


#: The span registry: every span a Sea layer records, with its parent span
#: (None: a root) and what it times. ``snapshot()["spans"]`` carries every
#: name here; ``seacheck``'s telemetry-drift rule checks that each literal
#: name passed to ``span``/``record_span`` is a key and each key is used.
SPANS: dict[str, tuple[str | None, str]] = {
    "sea.open.read": (None, "SeaFS.open of a read handle, fast or slow path"),
    "sea.open.write": (None, "SeaFS.open of a write handle: resolution, admission, the open"),
    "sea.admit": ("sea.open.write", "placement admission: place_new or reserve_write"),
    "sea.read": (None, "one read/readinto/readline call of a read handle; bytes returned"),
    "sea.write": (None, "one write call of a write handle; bytes written"),
    "sea.close": (None, "write-handle commit on close: size, ledger, resolver, health, "
                        "federation, close listeners"),
    "flush.queued": (None, "a flushed or evicted key's wait from Flusher.submit to pickup"),
    "flush.move": (None, "Flusher.process of a cache-resident key past its busy check: "
                         "the flush and/or evict; bytes moved"),
    "feed.put": (None, "device_iter's feeder thread putting one batch (put_fn)"),
    "feed.wait": (None, "device_iter's consumer blocked on an empty feed"),
}


class _Span:
    """One live span (spans on): times its ``with`` block on the wall and
    thread-CPU clocks and adds both, with ``nbytes``, to this thread's
    block; a TraceAnnotation of the same name where jax is imported."""

    __slots__ = ("_tel", "name", "nbytes", "_ann", "_t0", "_c0")

    def __init__(self, tel: "Telemetry", name: str, nbytes: int):
        self._tel = tel
        self.name = name
        self.nbytes = nbytes

    def __enter__(self) -> "_Span":
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._ann = ann = profiler.TraceAnnotation(self.name) if profiler else None
        if ann is not None:
            ann.__enter__()
        self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        c1 = time.thread_time_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tel.local().add_span(self.name, t1 - self._t0, c1 - self._c0, self.nbytes)


class _NoSpan:
    """The span of a site while spans are off: enters and exits with no
    clock and no lock; ``nbytes`` set on it goes nowhere."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    @property
    def nbytes(self) -> int:
        return 0

    @nbytes.setter
    def nbytes(self, value: int) -> None:
        pass


_NO_SPAN = _NoSpan()


#: The canonical counter registry: every scalar counter ``Telemetry``
#: carries, with its meaning. This table is the single source of truth —
#: ``snapshot()`` iterates it (a counter missing here silently vanishes
#: from exports, so it must not be missable), and ``seacheck``'s
#: telemetry-drift rule cross-checks it lexically against the dataclass
#: fields and every increment site, in both directions. Add a counter by
#: adding the field AND the registry row; the lint gate fails on either
#: half alone.
COUNTERS: dict[str, str] = {
    "transfer_orphans_reaped": "dead .sea_tmp staging files swept",
    "flushed_bytes": "bytes flushed cache->base",
    "flushed_files": "files flushed cache->base",
    "flush_failures": "flushes abandoned after exhausting retries",
    "evicted_bytes": "bytes evicted from cache tiers",
    "evicted_files": "files evicted from cache tiers",
    "prefetched_bytes": "bytes staged by static prefetch lists",
    "redirect_hits": "paths under the mount that Sea translated",
    "passthrough": "paths outside the mount (left untouched)",
    "ledger_hits": "O(1) capacity queries answered by the ledger",
    "ledger_reconciles": "full-root walks (reconcile path only)",
    "resolver_hits": "resolutions served by the location index",
    "resolver_misses": "full probe cascades (cold or invalidated)",
    "resolver_negative_hits": "misses absorbed by the negative cache",
    "resolver_verify_fails": "cached paths that vanished (file moved)",
    "resolver_invalidations": "entries dropped by mutation paths",
    "dir_index_hits": "listdir unions served by the child index",
    "dir_index_misses": "listdir unions that re-walked the roots",
    "readahead_predictions": "speculative keys the predictor emitted",
    "readahead_staged_files": "predictions whose staging copy committed",
    "readahead_staged_bytes": "bytes speculatively staged base->cache",
    "readahead_hits": "predicted keys subsequently opened",
    "readahead_hit_bytes": "staged bytes that were then read hot",
    "readahead_wasted_bytes": "staged bytes expired/cancelled unread",
    "extent_hits": "reads served from a staged extent",
    "extent_hit_bytes": "bytes those reads served from cache",
    "extent_misses": "reads that found the extent unstaged",
    "extent_miss_bytes": "bytes served from the base fallback",
    "extents_staged": "extents whose staging copy committed",
    "extent_staged_bytes": "bytes staged base->cache per-extent",
    "extents_punched": "staged extents evicted by punch-hole",
    "extent_punched_bytes": "bytes those punches deallocated",
    "extent_promotions": "part files completed -> whole replicas",
    "peer_hits": "local misses served by a peer's cache",
    "peer_pull_bytes": "bytes pulled peer->cache",
    "peer_fallbacks": "peer pulls that failed and fell back to base",
    "fastpath_opens": "read opens served by the lock-free fast path",
    "fastpath_redirect_hits": "redirects taken on the fast path",
    "ckpt_save_s": "seconds the step loop was blocked in save",
    "ckpt_bytes": "checkpoint leaf payload bytes written",
    "ckpt_overlap_hits": "async saves that finished with no waiter",
    "ckpt_restore_fallbacks": "corrupt checkpoints discarded by restore",
    "device_feed_stalls": "device_iter consumers that found the feed empty",
    "root_quarantines": "cache roots newly quarantined by the circuit breaker",
    "breaker_opens": "breaker open transitions (incl. half-open re-trips)",
    "degraded_reads": "reads rerouted around a sick root (other root/peer/base)",
    "deadline_aborts": "transfers aborted by the progress-deadline watchdog",
    "hung_thread_joins": "worker threads still alive after a bounded stop() join",
}


@dataclass
class Telemetry:
    per_tier: dict[str, TierCounters] = field(
        default_factory=lambda: defaultdict(TierCounters)
    )
    transfers: dict[str, TransferCounters] = field(
        default_factory=lambda: defaultdict(TransferCounters)
    )
    transfer_orphans_reaped: int = 0  # dead .sea_tmp staging files swept
    flushed_bytes: int = 0
    flushed_files: int = 0
    flush_failures: int = 0    # flushes abandoned after exhausting retries
    evicted_bytes: int = 0
    evicted_files: int = 0
    prefetched_bytes: int = 0
    redirect_hits: int = 0     # paths under the mount that Sea translated
    passthrough: int = 0       # paths outside the mount (left untouched)
    ledger_hits: int = 0       # O(1) capacity queries answered by the ledger
    ledger_reconciles: int = 0  # full-root walks (reconcile path only)
    resolver_hits: int = 0          # resolutions served by the location index
    resolver_misses: int = 0        # full probe cascades (cold or invalidated)
    resolver_negative_hits: int = 0  # misses absorbed by the negative cache
    resolver_verify_fails: int = 0  # cached paths that vanished (file moved)
    resolver_invalidations: int = 0  # entries dropped by mutation paths
    dir_index_hits: int = 0         # listdir unions served by the child index
    dir_index_misses: int = 0       # listdir unions that re-walked the roots
    readahead_predictions: int = 0  # speculative keys the predictor emitted
    readahead_staged_files: int = 0  # predictions whose staging copy committed
    readahead_staged_bytes: int = 0  # bytes speculatively staged base->cache
    readahead_hits: int = 0         # predicted keys subsequently opened
    readahead_hit_bytes: int = 0    # staged bytes that were then read hot
    readahead_wasted_bytes: int = 0  # staged bytes expired/cancelled unread
    extent_hits: int = 0            # reads served from a staged extent
    extent_hit_bytes: int = 0       # bytes those reads served from cache
    extent_misses: int = 0          # reads that found the extent unstaged
    extent_miss_bytes: int = 0      # bytes served from the base fallback
    extents_staged: int = 0         # extents whose staging copy committed
    extent_staged_bytes: int = 0    # bytes staged base->cache per-extent
    extents_punched: int = 0        # staged extents evicted by punch-hole
    extent_punched_bytes: int = 0   # bytes those punches deallocated
    extent_promotions: int = 0      # part files completed -> whole replicas
    peer_hits: int = 0              # local misses served by a peer's cache
    peer_pull_bytes: int = 0        # bytes pulled peer->cache
    peer_fallbacks: int = 0         # peer pulls that failed (peer died or
                                    # evicted mid-pull) and fell back to base
    fastpath_opens: int = 0         # read opens served by the lock-free
                                    # fast path (base: folded dead threads)
    fastpath_redirect_hits: int = 0  # redirects taken on the fast path
                                     # (base: folded dead threads)
    ckpt_save_s: float = 0.0        # seconds the step loop was blocked in
                                    # CheckpointManager.save (async saves
                                    # count only snapshot + handoff)
    ckpt_bytes: int = 0             # checkpoint leaf payload bytes written
    ckpt_overlap_hits: int = 0      # async saves whose background write
                                    # finished with no caller blocked on the
                                    # handle (the overlap fully hid the I/O)
    ckpt_restore_fallbacks: int = 0  # checkpoints discarded by restore_latest
                                     # (corrupt/partial) before an older step
                                     # restored
    device_feed_stalls: int = 0     # device_iter consumers that found the
                                    # feed queue empty (compute outran the
                                    # host->device stage)
    root_quarantines: int = 0       # cache roots newly quarantined (closed ->
                                    # open breaker transitions)
    breaker_opens: int = 0          # every open transition, including a
                                    # half-open probe failing back to open
    degraded_reads: int = 0         # reads served from another root, a peer,
                                    # or base because the placed root is sick
    deadline_aborts: int = 0        # copies aborted because no chunk progress
                                    # happened within transfer_deadline_s
    hung_thread_joins: int = 0      # stop() joins that timed out with the
                                    # worker thread still alive
    #: span sites record only while this is set (``trace_spans``)
    spans_on: bool = False
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    _tls: threading.local = field(default_factory=threading.local, repr=False)
    _locals: list = field(default_factory=list, repr=False)
    #: span name -> [count, wall_ns, cpu_ns, bytes] of dead threads' blocks
    _span_totals: dict = field(default_factory=dict, repr=False)

    def record_io(self, tier: str, *, read: int = 0, written: int = 0) -> None:
        with self._lock:
            c = self.per_tier[tier]
            if read:
                c.bytes_read += read
                c.files_read += 1
            if written:
                c.bytes_written += written
                c.files_written += 1

    def record_transfer(
        self, pair: str, *, nbytes: int, seconds: float = 0.0, retries: int = 0
    ) -> None:
        """One committed engine transfer over a ``"src->dst"`` tier pair —
        ``nbytes / seconds`` is that pair's observed bytes/sec."""
        with self._lock:
            c = self.transfers[pair]
            c.nbytes += nbytes
            c.files += 1
            c.seconds += seconds
            c.retries += retries

    def record_orphan_reaped(self) -> None:
        with self._lock:
            self.transfer_orphans_reaped += 1

    def transfer_rate_bps(self, pair: str) -> float:
        """Observed mean bytes/sec of one tier pair (0 when unmeasured)."""
        with self._lock:
            c = self.transfers.get(pair)
            if c is None or c.seconds <= 0:
                return 0.0
            return c.nbytes / c.seconds

    def record_flush(self, nbytes: int) -> None:
        with self._lock:
            self.flushed_bytes += nbytes
            self.flushed_files += 1

    def record_flush_failure(self) -> None:
        with self._lock:
            self.flush_failures += 1

    def record_evict(self, nbytes: int) -> None:
        with self._lock:
            self.evicted_bytes += nbytes
            self.evicted_files += 1

    def record_prefetch(self, nbytes: int) -> None:
        with self._lock:
            self.prefetched_bytes += nbytes

    def record_redirect(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.redirect_hits += 1
            else:
                self.passthrough += 1

    def record_ledger_hit(self) -> None:
        with self._lock:
            self.ledger_hits += 1

    def record_ledger_reconcile(self) -> None:
        with self._lock:
            self.ledger_reconciles += 1

    def record_resolve(
        self, *, hit: bool, negative: bool = False, verify_failed: bool = False
    ) -> None:
        with self._lock:
            if hit:
                self.resolver_hits += 1
                if negative:
                    self.resolver_negative_hits += 1
            else:
                self.resolver_misses += 1
                if verify_failed:
                    self.resolver_verify_fails += 1

    def record_resolver_invalidate(self) -> None:
        with self._lock:
            self.resolver_invalidations += 1

    def record_dir_resolve(self, *, hit: bool) -> None:
        with self._lock:
            if hit:
                self.dir_index_hits += 1
            else:
                self.dir_index_misses += 1

    # -- readahead (predictive prefetch) ------------------------------------
    def record_readahead_prediction(self) -> None:
        with self._lock:
            self.readahead_predictions += 1

    def record_readahead_staged(self, nbytes: int) -> None:
        with self._lock:
            self.readahead_staged_files += 1
            self.readahead_staged_bytes += nbytes

    def record_readahead_hit(self, nbytes: int, *, count: bool = True) -> None:
        """``count=False`` back-fills bytes for a hit already counted
        (the staging copy committed after the predicted open)."""
        with self._lock:
            if count:
                self.readahead_hits += 1
            self.readahead_hit_bytes += nbytes

    def record_readahead_waste(self, nbytes: int) -> None:
        with self._lock:
            self.readahead_wasted_bytes += nbytes

    # -- extent plane (block-granular staging) -------------------------------
    def record_extent_read(self, *, hit: bool, nbytes: int = 0) -> None:
        with self._lock:
            if hit:
                self.extent_hits += 1
                self.extent_hit_bytes += nbytes
            else:
                self.extent_misses += 1
                self.extent_miss_bytes += nbytes

    def record_extent_staged(self, nbytes: int) -> None:
        with self._lock:
            self.extents_staged += 1
            self.extent_staged_bytes += nbytes

    def record_extent_punched(self, nbytes: int) -> None:
        with self._lock:
            self.extents_punched += 1
            self.extent_punched_bytes += nbytes

    def record_extent_promoted(self) -> None:
        with self._lock:
            self.extent_promotions += 1

    # -- federation (peer-aware miss resolution) -----------------------------
    def record_peer_hit(self, nbytes: int) -> None:
        with self._lock:
            self.peer_hits += 1
            self.peer_pull_bytes += nbytes

    def record_peer_fallback(self) -> None:
        with self._lock:
            self.peer_fallbacks += 1

    # -- training I/O (checkpoint writer + device feed) ----------------------
    def record_ckpt_save(self, seconds: float, nbytes: int = 0) -> None:
        with self._lock:
            self.ckpt_save_s += seconds
            self.ckpt_bytes += nbytes

    def record_ckpt_overlap_hit(self) -> None:
        with self._lock:
            self.ckpt_overlap_hits += 1

    def record_ckpt_restore_fallback(self) -> None:
        with self._lock:
            self.ckpt_restore_fallbacks += 1

    def record_device_feed_stall(self) -> None:
        with self._lock:
            self.device_feed_stalls += 1

    def record_root_quarantine(self) -> None:
        with self._lock:
            self.root_quarantines += 1

    def record_breaker_open(self) -> None:
        with self._lock:
            self.breaker_opens += 1

    def record_degraded_read(self) -> None:
        with self._lock:
            self.degraded_reads += 1

    def record_deadline_abort(self) -> None:
        with self._lock:
            self.deadline_aborts += 1

    def record_hung_thread_join(self) -> None:
        with self._lock:
            self.hung_thread_joins += 1

    # -- spans -----------------------------------------------------------------
    def trace_spans(self, on: bool = True) -> None:
        """Turn span recording on or off (off by default)."""
        self.spans_on = bool(on)

    def span(self, name: str, nbytes: int = 0):
        """Context manager timing one ``name`` span (a ``SPANS`` key) on
        this thread; set ``.nbytes`` on it where the bytes are known only
        at the end. With spans off it costs one attribute check."""
        if not self.spans_on:
            return _NO_SPAN
        return _Span(self, name, nbytes)

    def record_span(self, name: str, seconds: float) -> None:
        """One ``name`` span of ``seconds`` on the wall clock, measured by
        the caller, for a duration that crosses threads (no CPU time)."""
        if self.spans_on:
            self.local().add_span(name, int(seconds * 1e9), 0, 0)

    # -- thread-batched fast-path counters ----------------------------------
    def local(self) -> ThreadCounters:
        """This thread's lock-free counter block (created and registered
        on first use). The open fast path writes here — one attribute
        store per event instead of a mutex round-trip."""
        lc = getattr(self._tls, "counters", None)
        if lc is None:
            lc = self._tls.counters = ThreadCounters()
            with self._lock:
                self._fold_dead_locked()
                self._locals.append(lc)
        return lc

    # seacheck: holds-lock
    def _fold_dead_locked(self) -> None:
        """Fold counter blocks of dead threads into the base counters and
        drop them (caller holds ``self._lock``). Safe: a dead thread can
        no longer write its block."""
        if all(lc.owner.is_alive() for lc in self._locals):
            return
        live = []
        for lc in self._locals:
            if lc.owner.is_alive():
                live.append(lc)
                continue
            self.redirect_hits += lc.redirect_hits
            self.fastpath_redirect_hits += lc.redirect_hits
            self.fastpath_opens += lc.fastpath_opens
            for tier, (nbytes, files) in lc.io_read.items():
                c = self.per_tier[tier]
                c.bytes_read += nbytes
                c.files_read += files
            _add_spans(self._span_totals, lc.spans)
        self._locals = live

    def snapshot(self) -> dict:
        with self._lock:
            self._fold_dead_locked()
            snap = {
                "tiers": {
                    k: vars(v).copy() for k, v in sorted(self.per_tier.items())
                },
                "transfers": {
                    k: vars(v).copy() for k, v in sorted(self.transfers.items())
                },
            }
            for name in COUNTERS:
                snap[name] = getattr(self, name)
            spans = {name: list(c) for name, c in self._span_totals.items()}
            locals_ = list(self._locals)
        # fold the LIVE per-thread fast-path blocks in (non-destructive
        # sums: the blocks only grow and are never reset, so no event is
        # ever double-counted or lost once its thread quiesces; dead
        # threads' blocks were folded into the base counters above)
        live_redirects = 0
        for lc in locals_:
            snap["fastpath_opens"] += lc.fastpath_opens
            snap["fastpath_redirect_hits"] += lc.redirect_hits
            live_redirects += lc.redirect_hits
            for tier in tuple(lc.io_read):
                nbytes, files = lc.io_read[tier]
                c = snap["tiers"].setdefault(tier, vars(TierCounters()).copy())
                c["bytes_read"] += nbytes
                c["files_read"] += files
            _add_spans(spans, dict(lc.spans))
        snap["redirect_hits"] += live_redirects
        snap["spans"] = {}
        for name in {**SPANS, **spans}:
            n, wall, cpu, nbytes = spans.get(name, (0, 0, 0, 0))
            snap["spans"][name] = {"count": n, "wall_s": wall / 1e9, "cpu_s": cpu / 1e9,
                                   "bytes": nbytes}
        return snap

    def export(self, path: str) -> str:
        """Write this process's snapshot (plus pid/timestamp) as JSON —
        atomically, so a concurrent aggregator never reads a torn file."""
        snap = self.snapshot()
        snap["pid"] = os.getpid()
        snap["exported_at"] = time.time()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f)
        os.replace(tmp, path)
        return path


def _add_spans(into: dict, blocks: dict) -> None:
    """Add per-name ``[count, wall_ns, cpu_ns, bytes]`` blocks into ``into``."""
    for name, c in blocks.items():
        t = into.setdefault(name, [0, 0, 0, 0])
        for i, v in enumerate(c):
            t[i] += v


def aggregate_snapshots(snapshots: list[dict]) -> dict:
    """Merge per-process snapshots into one aggregate view: numeric
    counters sum (per tier and global); pids are collected for attribution."""
    agg: dict = {"tiers": {}, "transfers": {}, "spans": {}, "pids": []}
    for snap in snapshots:
        if "pid" in snap:
            agg["pids"].append(snap["pid"])
        for section in ("tiers", "transfers", "spans"):
            for name, counters in snap.get(section, {}).items():
                out = agg[section].setdefault(name, defaultdict(float))
                for k, v in counters.items():
                    out[k] += v
        for k, v in snap.items():
            if k in ("tiers", "transfers", "spans", "pid", "exported_at"):
                continue
            if isinstance(v, (int, float)):
                agg[k] = agg.get(k, 0) + v
    for section in ("tiers", "transfers", "spans"):
        agg[section] = {t: dict(c) for t, c in agg[section].items()}
    agg["pids"].sort()
    return agg


def load_aggregate(stats_dir: str) -> dict:
    """Aggregate every exported per-process snapshot under ``stats_dir``
    (the ``.sea_ledger/telemetry/`` directory of a shared hierarchy)."""
    snaps = []
    try:
        names = sorted(os.listdir(stats_dir))
    except FileNotFoundError:
        names = []
    for fn in names:
        if not fn.endswith(".json"):
            continue
        try:
            with open(os.path.join(stats_dir, fn)) as f:
                snaps.append(json.load(f))
        except (OSError, ValueError):
            continue
    return aggregate_snapshots(snaps)

