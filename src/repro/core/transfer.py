"""TransferEngine — the data plane: every byte moved between tiers.

PRs 1–3 made Sea's *metadata* hot paths O(1); the actual bytes, however,
still moved through five independent synchronous ``shutil.copyfile`` call
sites (cross-mount rename, persist, flush, prefetch, pipeline staging)
with divergent atomicity, locking, and capacity-accounting semantics.
This module unifies them behind one engine, following the chunked,
overlapped-transfer designs of the HSM follow-up work (Hayot-Sasson &
Glatard 2024) and the openPMD/ADIOS2 streaming pipelines (Poeschel et
al. 2021):

* **Chunked copies** via ``os.copy_file_range`` (zero userspace copies,
  reflink/server-side offload where the filesystem supports it), falling
  back per-transfer to ``os.sendfile`` and finally to buffered
  read/write — the same chunk loop serves throttling, cancellation, and
  fault injection.
* **Admission before bytes move**: the capacity ledger's ``try_reserve``
  runs *before* the first chunk; the reservation is committed with the
  actual on-disk size after the rename, and released on any failure — a
  transfer can never over-commit a capped root or leak budget.
* **Crash-safe commit**: chunks land in a
  ``<dst>.<host>.<pid>.<seq>.sea_tmp`` staging file and the destination
  appears atomically via ``os.replace`` after a size verify, so a
  concurrent reader (or a crash at any chunk boundary) never observes a
  partial file. Orphaned staging files from dead processes are swept by
  :meth:`maybe_reap_orphan` (pid liveness on the owning host, age grace
  everywhere else).
* **Bounded parallelism with backpressure**: a lazy pool of
  ``transfer_workers`` threads executes submitted jobs; the submission
  queue is bounded, so producers block instead of buffering unbounded
  work (the prefetcher's overlap win lives here).
* **Per-tier-pair bandwidth throttling**: token buckets keyed
  ``"src->dst"`` (``SeaConfig.transfer_bandwidth_caps``) pace the chunk
  loop so background flushes can be capped below application I/O.
* **Retry with backoff** on transient ``OSError``; cooperative
  **cancellation** between chunks.
"""

from __future__ import annotations

import errno
import itertools
import os
import queue
import shutil
import socket
import threading
import time

from . import faults
from .config import SeaConfig
from .faults import FALLBACK_ERRNOS, TRANSIENT, classify
from .ledger import TMP_SUFFIX as _TMP_SUFFIX
from .telemetry import Telemetry
from .tiers import Tier

#: errno classification lives in repro.core.faults (one shared table for
#: the engine's retry loop, the flusher's backoff, and the breaker trips);
#: the historical module-private names stay as aliases.
_FALLBACK_ERRNOS = FALLBACK_ERRNOS
_PERMANENT_ERRNOS = faults.PERMANENT_ERRNOS

_HAS_COPY_FILE_RANGE = hasattr(os, "copy_file_range")
_HAS_SENDFILE = hasattr(os, "sendfile")

#: unique staging-file sequence within this process
_TMP_SEQ = itertools.count()

#: host tag embedded in staging-file names — pid liveness is only
#: meaningful on the node that created the file (tiers may be shared
#: parallel file systems); dots are stripped so the name stays parseable
_HOST = (socket.gethostname() or "localhost").replace(".", "-") or "localhost"

#: age past which a staging file not provably owned by a live local
#: process is declared dead. In-flight transfers keep their tmp's mtime
#: fresh (every chunk is a write), so age is a safe cross-node signal.
ORPHAN_GRACE_S = 300.0

#: source-side tier label of a federation peer pull — the bandwidth-cap
#: pair becomes "peer-><cache tier>" (wildcards "peer->*" / "*" apply),
#: so cluster pulls are throttled independently of local tier moves
PEER_TIER = "peer"


class TransferError(OSError):
    """A transfer failed after exhausting its retries."""


class TransferAdmissionError(TransferError):
    """The destination root refused the ledger reservation (no room)."""


class TransferCancelled(TransferError):
    """The transfer's cancel event fired between chunks."""


class TransferDeadlineError(TransferError):
    """The chunk loop made no progress for ``transfer_deadline_s``: the
    watchdog aborted the copy, the reservation was released, and the
    destination root's breaker was tripped."""


class _WatchEntry:
    """One in-flight copy under the progress-deadline watchdog."""

    __slots__ = ("progress", "deadline_s", "cancel", "tripped")

    def __init__(self, cancel: threading.Event, deadline_s: float):
        self.progress = time.monotonic()  # last chunk completion
        self.deadline_s = deadline_s
        self.cancel = cancel
        self.tripped = False


class TransferResult:
    """Outcome of one committed transfer."""

    __slots__ = ("nbytes", "seconds", "attempts", "impl")

    def __init__(self, nbytes: int, seconds: float, attempts: int, impl: str):
        self.nbytes = nbytes
        self.seconds = seconds
        self.attempts = attempts
        self.impl = impl

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"TransferResult(nbytes={self.nbytes}, seconds={self.seconds:.4f}, "
            f"attempts={self.attempts}, impl={self.impl!r})"
        )


class _TokenBucket:
    """Bytes/sec pacing for one tier pair. ``consume`` debits the bucket
    and returns how long the caller must sleep to honour the cap — the
    sleep happens outside the lock so concurrent transfers sharing a pair
    serialize only the arithmetic, not the wait."""

    def __init__(self, rate_bps: float):
        self.rate = float(rate_bps)
        self._lock = threading.Lock()
        self._available = self.rate * 0.05  # small burst allowance
        self._ts = time.monotonic()

    def consume(self, nbytes: int) -> float:
        with self._lock:
            now = time.monotonic()
            self._available = min(
                self._available + (now - self._ts) * self.rate, self.rate * 0.25
            )
            self._ts = now
            self._available -= nbytes
            if self._available >= 0:
                return 0.0
            return -self._available / self.rate


class _Future:
    """Minimal completion handle for a submitted transfer job."""

    __slots__ = ("_done", "_result", "_exc", "cancel_event")

    def __init__(self):
        self._done = threading.Event()
        self._result = None
        self._exc: BaseException | None = None
        self.cancel_event = threading.Event()

    def cancel(self) -> None:
        """Request cooperative cancellation (checked between chunks)."""
        self.cancel_event.set()

    def _finish(self, result=None, exc: BaseException | None = None) -> None:
        self._result = result
        self._exc = exc
        self._done.set()

    def result(self, timeout: float | None = None):
        if not self._done.wait(timeout):
            raise TimeoutError("transfer job still running")
        if self._exc is not None:
            raise self._exc
        return self._result

    def done(self) -> bool:
        return self._done.is_set()


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except (PermissionError, OSError):
        return True
    return True


def tmp_owner(path: str) -> tuple[str, int] | None:
    """Parse the owning (host, pid) out of a
    ``<dst>.<host>.<pid>.<seq>.sea_tmp`` staging-file name. Returns None
    for names the engine did not produce (reaping then falls back to the
    age grace — a numeric suffix in user data must never be mistaken for
    a dead pid)."""
    if not path.endswith(_TMP_SUFFIX):
        return None
    parts = path[: -len(_TMP_SUFFIX)].rsplit(".", 3)
    if len(parts) == 4 and parts[-1].isdigit() and parts[-2].isdigit():
        host = parts[-3]
        if host and "/" not in host:
            return host, int(parts[-2])
    return None


class TransferEngine:
    """One engine per :class:`~repro.core.seafs.SeaFS` instance. The
    engine owns byte movement only; callers keep resolver/telemetry
    semantics (key locks, ``note_location``, flush/prefetch counters)."""

    def __init__(
        self,
        config: SeaConfig,
        telemetry: Telemetry | None = None,
        policy=None,
    ):
        self.chunk_bytes = int(getattr(config, "transfer_chunk_bytes", 32 << 20))
        self.n_workers = max(1, int(getattr(config, "transfer_workers", 4)))
        self.retries = max(0, int(getattr(config, "transfer_retries", 2)))
        self.backoff_s = float(getattr(config, "transfer_backoff_s", 0.02))
        self.deadline_s = float(getattr(config, "transfer_deadline_s", 0.0))
        self.telemetry = telemetry or Telemetry()
        self.policy = policy  # bound by SeaFS after PlacementPolicy exists
        self.health = None  # HealthTracker, bound by SeaFS (optional)
        self._caps_spec = dict(getattr(config, "transfer_bandwidth_caps", {}) or {})
        self._buckets: dict[str, _TokenBucket] = {}
        self._bucket_lock = threading.Lock()
        #: staging paths of in-flight transfers in THIS process — the
        #: orphan reaper must never kill a live transfer's tmp file
        self._active_tmps: set[str] = set()
        self._active_lock = threading.Lock()
        #: fault-injection / instrumentation hook, called after every
        #: committed chunk as ``hook(copied_bytes, total_bytes, dst)``;
        #: an exception it raises fails the transfer like an I/O error
        self.chunk_hook = None
        #: lazy bounded worker pool
        self._q: "queue.Queue" = queue.Queue(maxsize=self.n_workers * 2)
        self._threads: list[threading.Thread] = []
        self._pool_lock = threading.Lock()
        #: progress-deadline watchdog (armed only when transfer_deadline_s>0)
        self._watch: set[_WatchEntry] = set()
        self._watch_lock = threading.Lock()
        self._watch_thread: threading.Thread | None = None

    # -- worker pool ---------------------------------------------------------
    def _ensure_pool(self) -> None:
        with self._pool_lock:
            self._threads = [t for t in self._threads if t.is_alive()]
            while len(self._threads) < self.n_workers:
                t = threading.Thread(
                    target=self._worker,
                    name=f"sea-transfer-{len(self._threads)}",
                    daemon=True,
                )
                t.start()
                self._threads.append(t)

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, args, kwargs, fut = item
            try:
                fut._finish(result=fn(*args, **kwargs))
            except BaseException as e:  # delivered through Future.result
                fut._finish(exc=e)

    def submit(self, fn, /, *args, **kwargs) -> _Future:
        """Run ``fn(*args, cancel=..., **kwargs)`` on the bounded pool.
        The queue is bounded at ``2 x workers``: a producer that outruns
        the device blocks here instead of buffering unbounded work
        (backpressure). ``fn`` receives the future's cancel event as a
        ``cancel`` keyword when it accepts one (``copy`` does)."""
        self._ensure_pool()
        fut = _Future()
        self._q.put((fn, args, kwargs, fut))
        return fut

    def try_submit(self, fn, /, *args, **kwargs) -> _Future | None:
        """Non-blocking :meth:`submit`: returns None when the bounded
        queue is full instead of blocking the caller. For producers that
        must never stall behind other producers sharing the pool (the
        readahead predictor's digestion thread drops the speculative job
        instead)."""
        self._ensure_pool()
        fut = _Future()
        try:
            self._q.put_nowait((fn, args, kwargs, fut))
        except queue.Full:
            return None
        return fut

    def submit_copy(self, src: str, dst: str, /, **kwargs) -> _Future:
        """``submit`` specialised to :meth:`copy`, wiring the future's
        cancel event into the chunk loop."""
        self._ensure_pool()
        fut = _Future()
        kwargs.setdefault("cancel", fut.cancel_event)
        self._q.put((self.copy, (src, dst), kwargs, fut))
        return fut

    def map(self, fn, items) -> list:
        """Run ``fn(item)`` for every item on the pool and collect results
        in order; exceptions propagate after all jobs settle."""
        futs = [self.submit(fn, item) for item in items]
        out, first_exc = [], None
        for f in futs:
            try:
                out.append(f.result())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                if first_exc is None:
                    first_exc = e
                out.append(None)
        if first_exc is not None:
            raise first_exc
        return out

    def close(self) -> None:
        """Stop the worker pool (restarts lazily on the next submit)."""
        with self._pool_lock:
            threads, self._threads = self._threads, []
        for _ in threads:
            self._q.put(None)
        for t in threads:
            t.join(timeout=10)

    # -- progress-deadline watchdog ------------------------------------------
    def _deadline_guard(self, cancel, on_chunk):
        """Arm the watchdog for one copy when ``transfer_deadline_s`` is set.

        Returns ``(cancel, on_chunk, entry)``: the (possibly new) cancel
        event the watchdog will set on a stall, an ``on_chunk`` wrapper that
        stamps per-chunk progress, and the watch entry to unregister (None
        when deadlines are disabled).  The abort is cooperative — the chunk
        loop (and cancel-aware injected hangs) observe the event between
        chunks; a thread wedged *inside* a blocking syscall cannot be
        interrupted from Python and is documented as out of contract.
        Note the deadline measures *stall*, not total duration: a heavily
        token-bucket-throttled transfer must configure a deadline above its
        worst-case per-chunk wait.
        """
        if self.deadline_s <= 0:
            return cancel, on_chunk, None
        if cancel is None:
            cancel = threading.Event()
        entry = _WatchEntry(cancel, self.deadline_s)

        def stamped(copied, total, path, _e=entry, _inner=on_chunk):
            _e.progress = time.monotonic()
            if _inner is not None:
                _inner(copied, total, path)

        with self._watch_lock:
            self._watch.add(entry)
            t = self._watch_thread
            if t is None or not t.is_alive():
                t = threading.Thread(
                    target=self._watchdog, name="sea-transfer-watchdog", daemon=True
                )
                self._watch_thread = t
                t.start()
        return cancel, stamped, entry

    def _watch_unregister(self, entry: _WatchEntry) -> None:
        with self._watch_lock:
            self._watch.discard(entry)

    def _watchdog(self) -> None:
        while True:
            with self._watch_lock:
                if not self._watch:
                    # nothing left to watch: exit instead of ticking for
                    # the life of the process. Clearing _watch_thread
                    # under the lock lets _deadline_guard respawn the
                    # thread race-free on the next armed copy.
                    self._watch_thread = None
                    return
                entries = list(self._watch)
            now = time.monotonic()
            tick = 0.25
            for e in entries:
                if e.tripped:
                    continue
                stalled = now - e.progress
                if stalled >= e.deadline_s:
                    e.tripped = True
                    e.cancel.set()
                else:
                    tick = min(tick, max(0.005, (e.deadline_s - stalled) / 4))
            time.sleep(tick)

    def _deadline_abort(self, entry, src, dst, root, cause) -> "TransferDeadlineError":
        """Account a watchdog trip: telemetry + breaker, build the error."""
        self.telemetry.record_deadline_abort()
        if root is not None and self.health is not None:
            self.health.trip(root, "deadline")
        err = TransferDeadlineError(
            errno.ETIMEDOUT,
            f"transfer {src} -> {dst} made no progress for {entry.deadline_s}s",
        )
        err.__cause__ = cause
        return err

    # -- throttling ----------------------------------------------------------
    def _pair_cap(self, pair: str) -> float:
        src, _, dst = pair.partition("->")
        for k in (pair, f"{src}->*", f"*->{dst}", "*"):
            if k in self._caps_spec:
                return float(self._caps_spec[k])
        return 0.0

    def _bucket(self, pair: str) -> _TokenBucket | None:
        rate = self._pair_cap(pair)
        if rate <= 0:
            return None
        with self._bucket_lock:
            b = self._buckets.get(pair)
            if b is None:
                b = self._buckets[pair] = _TokenBucket(rate)
            return b

    # -- the transfer primitive ----------------------------------------------
    @staticmethod
    def _tier_name(tier) -> str:
        if tier is None:
            return "ext"
        return tier.name if isinstance(tier, Tier) else str(tier)

    def copy(
        self,
        src: str,
        dst: str,
        *,
        src_tier: Tier | str | None = None,
        dst_tier: Tier | str | None = None,
        dst_root: str | None = None,
        key: str | None = None,
        admit: str | None = None,
        reservation=None,
        preserve_stat: bool = True,
        cancel: threading.Event | None = None,
        on_chunk=None,
    ) -> TransferResult:
        """Move ``src`` to ``dst`` atomically, with accounting.

        ``admit`` selects the ledger admission run *before* any byte moves
        (only meaningful when ``dst_tier`` is a :class:`Tier` with
        ``dst_root``):

        * ``"require"`` — ``try_reserve`` the source's actual size; raises
          :class:`TransferAdmissionError` when the root has no room
          (prefetch/staging callers skip the stage).
        * ``"reserve"`` — unconditional budget hold (flush/persist to the
          base tier: there is nowhere slower to go).
        * ``None`` — no engine-side admission; pass ``reservation`` when
          the caller already holds one (it is committed with the actual
          size on success and released on failure either way).

        On success the reservation (engine- or caller-held) is committed
        via ``Tier.commit_write`` — which also folds the actual size into
        the capacity ledger — and the staging file has been renamed over
        ``dst``. On any failure the staging file is unlinked and the
        reservation released; ``dst`` is untouched.
        """
        t0 = time.perf_counter()
        pair = f"{self._tier_name(src_tier)}->{self._tier_name(dst_tier)}"
        accounted = isinstance(dst_tier, Tier) and dst_root is not None
        res = reservation
        if cancel is not None and cancel.is_set():
            # a stale speculative transfer must not even take admission
            # or touch the source — but a caller-held reservation still
            # must not leak
            if res is not None and isinstance(dst_tier, Tier):
                dst_tier.release_write(res)
            raise TransferCancelled(f"transfer {src} -> {dst} cancelled")
        try:
            # the source must be readable before any admission or staging
            # — and its error propagates untranslated (callers rely on
            # POSIX semantics, e.g. FileNotFoundError from a cross-mount
            # rename). A caller-held reservation must not leak even here.
            src_size = os.stat(src).st_size
        except OSError:
            if res is not None and isinstance(dst_tier, Tier):
                dst_tier.release_write(res)
            raise
        if res is None and accounted and admit is not None:
            res = self._admit(dst_tier, dst_root, src_size, mode=admit)

        # per-root health: only cache destinations are tracked (base has no
        # "elsewhere" to degrade to, so its breaker would only hurt)
        health_root = (
            dst_root
            if self.health is not None and accounted and not dst_tier.spec.persistent
            else None
        )
        cancel, on_chunk, watch = self._deadline_guard(cancel, on_chunk)
        t1 = time.monotonic()
        try:
            nbytes, attempts, impl = self._copy_with_retries(
                src, dst, pair, preserve_stat, cancel, on_chunk
            )
        except BaseException as e:
            if res is not None and isinstance(dst_tier, Tier):
                dst_tier.release_write(res)
            if watch is not None and watch.tripped:
                raise self._deadline_abort(watch, src, dst, health_root, e) from e
            if (
                health_root is not None
                and isinstance(e, OSError)
                and not isinstance(e, (TransferCancelled, TransferAdmissionError))
                and e.errno != errno.ENOENT  # src vanished, not a sick root
            ):
                self.health.record_failure(health_root, e)
            raise
        finally:
            if watch is not None:
                self._watch_unregister(watch)
        if health_root is not None:
            self.health.record_success(health_root, time.monotonic() - t1)
        if accounted:
            if key is None:
                key = os.path.relpath(dst, dst_root)
            dst_tier.commit_write(res, dst_root, key, nbytes)
        elif res is not None and isinstance(dst_tier, Tier):
            # caller-held reservation with no root to commit against:
            # return the budget rather than leak it
            dst_tier.release_write(res)
        seconds = time.perf_counter() - t0
        self.telemetry.record_transfer(
            pair, nbytes=nbytes, seconds=seconds, retries=attempts - 1
        )
        return TransferResult(nbytes, seconds, attempts, impl)

    def peer_pull(
        self,
        src: str,
        dst: str,
        *,
        dst_tier: Tier,
        dst_root: str,
        key: str,
        cancel: threading.Event | None = None,
    ) -> TransferResult:
        """Pull a peer node's cache replica into a local cache tier —
        :meth:`copy` specialised to the federation path.

        The source tier is the symbolic :data:`PEER_TIER` (the replica
        lives in *another node's* hierarchy, which this engine has no
        Tier object for), so throttling uses the ``"peer-><dst>"``
        bandwidth-cap pair — cluster pulls get their own budget.
        Admission is ``"require"``: a full cache root skips the pull
        rather than evicting for it (the base fallback still serves).
        All of :meth:`copy`'s failure guarantees apply — a peer that
        dies or evicts mid-pull leaves no partial file, no leaked
        reservation, and ``dst`` untouched; the caller falls back to
        the base tier and expunges the registry entry.

        A configured ``transfer_deadline_s`` applies here too: a peer whose
        export hangs mid-pull trips the watchdog, the pull cancels, and the
        caller's OSError handler falls back to base."""
        faults.fire("federation.pull", path=src)
        return self.copy(
            src,
            dst,
            src_tier=PEER_TIER,
            dst_tier=dst_tier,
            dst_root=dst_root,
            key=key,
            admit="require",
            preserve_stat=True,
            cancel=cancel,
        )

    def copy_range(
        self,
        src: str,
        dst: str,
        offset: int,
        length: int,
        *,
        src_tier: Tier | str | None = None,
        dst_tier: Tier | str | None = None,
        dst_root: str | None = None,
        cancel: threading.Event | None = None,
        on_chunk=None,
    ) -> TransferResult:
        """Stream ``length`` bytes of ``src`` starting at ``offset`` into
        the same range of ``dst`` — the extent-staging primitive.

        ``dst_root`` (the cache root holding the extent part file) feeds
        the same per-root health/breaker accounting as :meth:`copy`: a
        deadline abort or I/O failure on an extent stage trips/records
        against the destination root exactly like a whole-file copy.

        Unlike :meth:`copy` there is no staging tmp and no rename:
        ``dst`` is a preallocated *sparse* destination (an extent plane
        part file) and the bytes are written in place at ``offset``.
        Atomicity is the caller's validity journal — it is updated only
        after this method returns, so a crash at any chunk boundary
        leaves the extent unmarked, never torn-but-valid. Ledger
        admission likewise stays with the caller (per-extent
        reservations, committed against the part file's disk usage).

        The chunk loop shares everything else with :meth:`copy`:
        ``copy_file_range`` with explicit offsets (buffered pread/pwrite
        fallback), the per-tier-pair token-bucket throttle,
        retry-with-backoff (re-copying a range is idempotent),
        cooperative ``cancel`` between chunks, and the
        ``chunk_hook``/``on_chunk`` fault-injection points."""
        t0 = time.perf_counter()
        pair = f"{self._tier_name(src_tier)}->{self._tier_name(dst_tier)}"
        if cancel is not None and cancel.is_set():
            raise TransferCancelled(f"range transfer {src} -> {dst} cancelled")
        # per-root health: same contract as copy() — only cache
        # destinations are tracked
        health_root = (
            dst_root
            if self.health is not None
            and dst_root is not None
            and isinstance(dst_tier, Tier)
            and not dst_tier.spec.persistent
            else None
        )
        cancel, on_chunk, watch = self._deadline_guard(cancel, on_chunk)
        delay = self.backoff_s
        last_exc: BaseException | None = None
        t1 = time.monotonic()
        try:
            for attempt in range(1, self.retries + 2):
                try:
                    copied, impl = self._copy_range_once(
                        src, dst, offset, length, pair, cancel, on_chunk
                    )
                except TransferCancelled as e:
                    if watch is not None and watch.tripped:
                        raise self._deadline_abort(
                            watch, src, dst, health_root, e
                        ) from e
                    raise
                except Exception as e:
                    last_exc = e
                    # transient errors retry; permanent and capacity
                    # (ENOSPC) classes fail fast — see repro.core.faults
                    if classify(e) is not TRANSIENT or attempt > self.retries:
                        break
                    if cancel is not None and cancel.is_set():
                        if watch is not None and watch.tripped:
                            raise self._deadline_abort(
                                watch, src, dst, health_root, e
                            ) from e
                        raise TransferCancelled(
                            f"range transfer to {dst} cancelled"
                        ) from e
                    time.sleep(delay)
                    delay *= 2
                else:
                    seconds = time.perf_counter() - t0
                    if health_root is not None:
                        self.health.record_success(
                            health_root, time.monotonic() - t1
                        )
                    self.telemetry.record_transfer(
                        pair, nbytes=copied, seconds=seconds, retries=attempt - 1
                    )
                    return TransferResult(copied, seconds, attempt, impl)
        finally:
            if watch is not None:
                self._watch_unregister(watch)
        if (
            health_root is not None
            and isinstance(last_exc, OSError)
            and last_exc.errno != errno.ENOENT  # src vanished, not a sick root
        ):
            self.health.record_failure(health_root, last_exc)
        if isinstance(last_exc, OSError):
            raise last_exc
        raise TransferError(
            f"range transfer {src}[{offset}:{offset + length}] -> {dst} "
            f"failed after {self.retries + 1} attempts"
        ) from last_exc

    def _copy_range_once(
        self, src, dst, offset, length, pair, cancel, on_chunk
    ) -> tuple[int, str]:
        bucket = self._bucket(pair)
        copied = 0
        impl = "copy_file_range" if _HAS_COPY_FILE_RANGE else "preadwrite"
        with open(src, "rb") as fi, open(dst, "r+b") as fo:
            ifd, ofd = fi.fileno(), fo.fileno()
            while copied < length:
                if cancel is not None and cancel.is_set():
                    raise TransferCancelled(f"range transfer of {src} cancelled")
                want = min(self.chunk_bytes, length - copied)
                pos = offset + copied
                if impl == "copy_file_range":
                    try:
                        n = os.copy_file_range(
                            ifd, ofd, want, offset_src=pos, offset_dst=pos
                        )
                    except OSError as e:
                        if e.errno in _FALLBACK_ERRNOS:
                            impl = "preadwrite"
                            continue
                        raise
                else:
                    buf = os.pread(ifd, want, pos)
                    n = len(buf)
                    if n:
                        os.pwrite(ofd, buf, pos)
                if n == 0:
                    break  # source shorter than the recorded extent map
                copied += n
                if on_chunk is not None:
                    on_chunk(copied, length, dst)
                if self.chunk_hook is not None:
                    self.chunk_hook(copied, length, dst)
                faults.fire("transfer.range_chunk", path=dst, cancel=cancel)
                if bucket is not None:
                    self._throttle_wait(bucket.consume(n), ofd)
        if copied != length:
            # the source changed size under the extent map: the caller's
            # map is stale and must be rebuilt, not marked valid
            raise TransferError(
                f"range verify failed for {src}[{offset}:{offset + length}]: "
                f"copied {copied}"
            )
        return copied, impl

    def _admit(self, tier: Tier, root: str, nbytes: int, *, mode: str):
        if mode == "reserve" or tier.spec.capacity is None:
            return tier.reserve_write(root, nbytes)
        required = (
            self.policy.required_bytes if self.policy is not None else nbytes
        )
        res = tier.ledger.try_reserve(
            root, nbytes, capacity=tier.spec.capacity, required=required
        )
        if res is None:
            raise TransferAdmissionError(
                f"no room for {nbytes} bytes on {tier.name}:{root}"
            )
        return res

    def _copy_with_retries(
        self, src, dst, pair, preserve_stat, cancel, on_chunk
    ) -> tuple[int, int, str]:
        delay = self.backoff_s
        last_exc: BaseException | None = None
        for attempt in range(1, self.retries + 2):
            tmp = f"{dst}.{_HOST}.{os.getpid()}.{next(_TMP_SEQ)}{_TMP_SUFFIX}"
            with self._active_lock:
                self._active_tmps.add(tmp)
            try:
                nbytes, impl = self._copy_once(
                    src, tmp, pair, cancel, on_chunk
                )
                if preserve_stat:
                    try:
                        shutil.copystat(src, tmp)
                    except OSError:
                        pass  # stat parity is best-effort (e.g. tmpfs xattrs)
                faults.fire("transfer.commit", path=dst)
                os.replace(tmp, dst)  # atomic commit
                return nbytes, attempt, impl
            except TransferCancelled:
                self._discard_tmp(tmp)
                raise
            except Exception as e:
                self._discard_tmp(tmp)
                last_exc = e
                # transient errors retry; permanent and capacity (ENOSPC)
                # classes fail fast — see repro.core.faults for the table
                if classify(e) is not TRANSIENT or attempt > self.retries:
                    break
                if cancel is not None and cancel.is_set():
                    raise TransferCancelled(f"transfer to {dst} cancelled") from e
                time.sleep(delay)
                delay *= 2
            finally:
                with self._active_lock:
                    self._active_tmps.discard(tmp)
        if isinstance(last_exc, OSError):
            # preserve the POSIX error class/errno the seed's bare copy
            # surfaced (callers match `except PermissionError`, check
            # e.errno, etc.); TransferError wraps only non-OS failures
            raise last_exc
        raise TransferError(
            f"transfer {src} -> {dst} failed after {self.retries + 1} attempts"
        ) from last_exc

    def _discard_tmp(self, tmp: str) -> None:
        try:
            os.unlink(tmp)
        except OSError:
            pass

    def _copy_once(self, src, tmp, pair, cancel, on_chunk) -> tuple[int, str]:
        """One staging attempt: chunk loop into ``tmp`` + size verify."""
        bucket = self._bucket(pair)
        copied = 0
        impl = (
            "copy_file_range"
            if _HAS_COPY_FILE_RANGE
            else ("sendfile" if _HAS_SENDFILE else "readwrite")
        )
        with open(src, "rb") as fi, open(tmp, "wb") as fo:
            ifd, ofd = fi.fileno(), fo.fileno()
            total = os.fstat(ifd).st_size
            while True:
                if cancel is not None and cancel.is_set():
                    raise TransferCancelled(f"transfer of {src} cancelled")
                if impl == "copy_file_range":
                    try:
                        n = os.copy_file_range(ifd, ofd, self.chunk_bytes)
                    except OSError as e:
                        if e.errno in _FALLBACK_ERRNOS:
                            impl = "sendfile" if _HAS_SENDFILE else "readwrite"
                            continue
                        raise
                elif impl == "sendfile":
                    try:
                        n = os.sendfile(ofd, ifd, None, self.chunk_bytes)
                    except OSError as e:
                        if e.errno in _FALLBACK_ERRNOS:
                            impl = "readwrite"
                            continue
                        raise
                else:
                    buf = fi.read(self.chunk_bytes)
                    n = len(buf)
                    if n:
                        fo.write(buf)
                if n == 0:
                    break
                copied += n
                if on_chunk is not None:
                    on_chunk(copied, total, tmp)
                if self.chunk_hook is not None:
                    self.chunk_hook(copied, total, tmp)
                faults.fire("transfer.chunk", path=tmp, cancel=cancel)
                if bucket is not None:
                    self._throttle_wait(bucket.consume(n), ofd)
        # size-verified completion: the committed file must hold exactly
        # what the source holds NOW (a mid-copy rewrite forces a retry)
        final = os.path.getsize(src)
        if copied != final:
            raise TransferError(
                f"size verify failed for {src}: copied {copied}, source {final}"
            )
        return copied, impl

    @staticmethod
    def _throttle_wait(wait: float, fd: int) -> None:
        """Sleep out a token-bucket debt in bounded slices, refreshing
        the staging file's mtime between slices — a heavily throttled
        transfer (one chunk's debt can exceed the orphan grace) must
        never look age-dead to another node's reaper."""
        slice_s = ORPHAN_GRACE_S / 4
        while wait > 0:
            time.sleep(min(wait, slice_s))
            wait -= slice_s
            if wait > 0:
                try:
                    os.utime(fd)
                except OSError:
                    pass

    # -- orphan staging files --------------------------------------------------
    def maybe_reap_orphan(self, path: str) -> bool:
        """Delete a ``*.sea_tmp`` staging file iff it is provably dead:
        created on THIS host by a pid that no longer exists, or untouched
        for :data:`ORPHAN_GRACE_S` (an in-flight transfer keeps its tmp's
        mtime fresh with every chunk, so age is safe even for files owned
        by another node of a shared tier). Anything else is left alone —
        the LRU and scan walks must never delete a half-written staging
        file out from under a racing ``os.replace``."""
        if not path.endswith(_TMP_SUFFIX):
            return False
        with self._active_lock:
            if path in self._active_tmps:
                return False
        owner = tmp_owner(path)
        local_dead = (
            owner is not None
            and owner[0] == _HOST
            and not _pid_alive(owner[1])
        )
        if not local_dead:
            # foreign host, unparseable name, or a live local pid (which
            # may be a RECYCLED pid squatting on a crashed writer's name):
            # fall back to the age grace. Safe for genuinely in-flight
            # transfers — every chunk write and every throttle slice
            # (_throttle_wait) keeps the tmp's mtime fresh.
            try:
                age = time.time() - os.path.getmtime(path)
            except OSError:
                return False
            if age < ORPHAN_GRACE_S:
                return False
        try:
            os.unlink(path)
        except OSError:
            return False
        self.telemetry.record_orphan_reaped()
        return True

    def sweep_orphans(self, root: str) -> int:
        """Walk one root and reap every provably-dead staging file."""
        n = 0
        for dirpath, _dirs, files in os.walk(root):
            for fn in files:
                if fn.endswith(_TMP_SUFFIX) and self.maybe_reap_orphan(
                    os.path.join(dirpath, fn)
                ):
                    n += 1
        return n
