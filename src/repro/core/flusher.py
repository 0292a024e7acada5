"""Background flush-and-evict daemon + prefetcher (paper §3.3, §5.1).

"If only a single instance of Sea is called on a compute node, there will
only be a single flush and evict process." — the paper pairs one worker
with each Sea instance; we generalise to a small worker pool
(``SeaConfig.flush_workers``) so flushes of *independent keys* proceed
concurrently while per-key ``key_lock`` serialisation keeps any single
file's flush/evict atomic.

The daemon reacts to file-close events and also runs stateless scans of
the cache tiers on demand (so files written before the daemon started, or
by other processes sharing the tiers, are still picked up). Flushes are
atomic: copy to ``<dst>.sea_tmp`` on the base tier, then ``os.replace``;
eviction of a MOVEd file happens only after the rename commits, so readers
resolving the hierarchy always find a complete copy (fixes the paper's
§5.5 in-flight-access limitation). Every flush/evict transactionally
updates the capacity ledger, keeping placement's O(1) free-space counters
truthful without a rescan.

**Single-flusher coordination** (``SeaConfig.shared_ledger``): the paper
notes that "if Sea is launched many times on a given node, there will be
many flush and evict processes" — racing duplicate flushers over the same
hierarchy. In shared mode exactly one elected leader per hierarchy runs
the daemon: leadership is an ``fcntl`` lock on
``<base_root>/.sea_ledger/flusher.lock`` plus a heartbeat file rewritten
every ``leader_heartbeat_s``. Followers enqueue their close events into a
spool directory the leader drains; on leader death (the kernel releases
the lock) a follower whose staleness check fires takes over within two
heartbeats, rescans the cache tiers, and drains the spool.
"""

from __future__ import annotations

import fcntl
import json
import os
import queue
import sys
import threading
import time
import traceback
from urllib.parse import quote, unquote

from . import faults
from .extents import PART_SUFFIX
from .faults import TRANSIENT, classify
from .ledger import LEDGER_DIRNAME, TMP_SUFFIX
from .lists import Mode
from .seafs import SeaFS

_TMP_SUFFIX = TMP_SUFFIX  # one canonical staging suffix (ledger.py)

#: leadership lock paths held by THIS process. fcntl locks are owned per
#: (process, inode): a second Flusher in the same process would "win" the
#: lock trivially and closing its fd would drop the first one's — so
#: in-process contenders are arbitrated here instead of through fcntl.
_HELD_LEADER_LOCKS: set[str] = set()
_HELD_LEADER_LOCKS_GUARD = threading.Lock()


def _since(stamp_ns: int) -> int:
    """Nanoseconds since ``stamp_ns`` (0 where the stamp is 0: unmeasured)."""
    return time.perf_counter_ns() - stamp_ns if stamp_ns else 0


class Flusher:
    def __init__(self, fs: SeaFS):
        self.fs = fs
        self.config = fs.config
        self.n_workers = max(1, int(getattr(fs.config, "flush_workers", 1)))
        self._q: "queue.Queue[str | None]" = queue.Queue()
        #: keys queued but not yet picked up -> submit time (perf_counter_ns,
        #: 0 while spans are off), read back as the ``flush.queued`` span
        self._pending: dict[str, int] = {}
        self._active: dict[str, bool] = {}  # being processed -> resubmit flag
        self._deferred: set[str] = set()  # skipped busy; await any close
        self._failed: dict[str, float] = {}  # key -> monotonic not-before:
                                             # failed flushes, retried on
                                             # idle ticks after a backoff
        self._draining = False            # suppress idle retries in drain()
        self._inflight = 0                # keys currently being processed
        self._cv = threading.Condition()  # guards the four fields above
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        #: cross-process coordination (shared_ledger mode only)
        self._coordinated = bool(getattr(fs.config, "shared_ledger", False))
        self._hb_interval = float(getattr(fs.config, "leader_heartbeat_s", 0.5))
        coord_dir = os.path.join(fs.hierarchy.base.roots[0], LEDGER_DIRNAME)
        self._lock_path = os.path.join(coord_dir, "flusher.lock")
        self._hb_path = os.path.join(coord_dir, "flusher.heartbeat")
        self._spool_dir = os.path.join(coord_dir, "spool")
        self._leader_fd: int | None = None
        self._leader_guard = threading.Lock()
        fs.add_close_listener(self._on_close)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "Flusher":
        if not self._alive():
            self._stop.clear()
            self._threads = [
                threading.Thread(
                    target=self._run, name=f"sea-flusher-{i}", daemon=True
                )
                for i in range(self.n_workers)
            ]
            if self._coordinated:
                self._try_acquire_leadership()
                self._threads.append(
                    threading.Thread(
                        target=self._coordinate, name="sea-coordinator", daemon=True
                    )
                )
            for t in self._threads:
                t.start()
        return self

    def stop(self) -> None:
        try:
            self._stop.set()
            for _ in self._threads:
                self._q.put(None)
            for t in self._threads:
                t.join(timeout=30)
                if t.is_alive():
                    # a worker wedged in hung I/O must not look like a
                    # clean stop: surface it and count it (the daemon
                    # thread is abandoned; process exit reaps it)
                    print(
                        f"sea: flusher thread {t.name} still alive after a "
                        "30s join — abandoning it",
                        file=sys.stderr,
                    )
                    self.fs.telemetry.record_hung_thread_join()
        finally:
            # leadership MUST be returned even if a worker join blew up,
            # or every surviving follower waits out a dead lockfile holder
            self._release_leadership()

    def _alive(self) -> bool:
        return any(t.is_alive() for t in self._threads)

    # -- leader election (shared_ledger mode) ---------------------------------
    @property
    def is_leader(self) -> bool:
        """In coordinated mode: does this instance hold the flusher lock?
        Uncoordinated instances are trivially their own leader."""
        if not self._coordinated:
            return True
        return self._leader_fd is not None

    def _try_acquire_leadership(self) -> bool:
        with self._leader_guard:
            if self._leader_fd is not None:
                return True
            # realpath: two spellings of the same base root (symlinked
            # scratch dirs) must arbitrate on one registry key, or both
            # "win" the per-process fcntl lock
            lock_key = os.path.realpath(self._lock_path)
            with _HELD_LEADER_LOCKS_GUARD:
                if lock_key in _HELD_LEADER_LOCKS:
                    return False  # another instance in THIS process leads
            os.makedirs(self._spool_dir, exist_ok=True)
            fd = os.open(self._lock_path, os.O_RDWR | os.O_CREAT, 0o644)
            try:
                fcntl.lockf(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(fd)
                return False
            with _HELD_LEADER_LOCKS_GUARD:
                _HELD_LEADER_LOCKS.add(lock_key)
            os.ftruncate(fd, 0)
            os.pwrite(fd, str(os.getpid()).encode(), 0)
            self._leader_fd = fd
        self._write_heartbeat()
        return True

    def _release_leadership(self) -> None:
        with self._leader_guard:
            fd, self._leader_fd = self._leader_fd, None
            if fd is None:
                return
            with _HELD_LEADER_LOCKS_GUARD:
                _HELD_LEADER_LOCKS.discard(os.path.realpath(self._lock_path))
            hb = self._read_heartbeat()
            if hb is not None and hb.get("pid") == os.getpid():
                try:
                    os.unlink(self._hb_path)
                except OSError:
                    pass
            try:
                fcntl.lockf(fd, fcntl.LOCK_UN)
            finally:
                os.close(fd)

    def _write_heartbeat(self) -> None:
        tmp = f"{self._hb_path}.{os.getpid()}{_TMP_SUFFIX}"
        try:
            with open(tmp, "w") as f:
                json.dump({"pid": os.getpid(), "ts": time.time()}, f)
            os.replace(tmp, self._hb_path)  # atomic: readers never see a torn file
        except OSError:
            pass

    def _read_heartbeat(self) -> dict | None:
        try:
            with open(self._hb_path) as f:
                hb = json.load(f)
            return hb if isinstance(hb, dict) else None
        except (OSError, ValueError):
            return None

    def _heartbeat_stale(self) -> bool:
        hb = self._read_heartbeat()
        if hb is None:
            return True
        return time.time() - float(hb.get("ts", 0)) > self._hb_interval

    def _coordinate(self) -> None:
        """Leader: beat + drain the spool. Follower: watch the heartbeat and
        take over once it goes stale (the fcntl lock is only obtainable
        after the leader process actually died, so trying early is safe)."""
        while not self._stop.wait(self._hb_interval / 2):
            if self.fs.federation is not None:
                # piggyback the cluster-membership heartbeat on the
                # coordination tick (leader and follower alike: every
                # instance is its own federation node)
                self.fs.federation.maybe_heartbeat()
            if self.is_leader:
                self._write_heartbeat()
                self._drain_spool()
            elif self._heartbeat_stale() and self._try_acquire_leadership():
                # takeover: recover everything the dead leader left behind
                self.scan()
                self._drain_spool()

    # -- follower spool ---------------------------------------------------------
    def _spool_submit(self, key: str) -> None:
        """Followers don't flush; they hand the key to the leader through
        the spool directory (one file per key — resubmits coalesce)."""
        os.makedirs(self._spool_dir, exist_ok=True)
        path = os.path.join(self._spool_dir, quote(key, safe=""))
        tmp = f"{path}.{os.getpid()}{_TMP_SUFFIX}"
        try:
            with open(tmp, "w") as f:
                f.write(key)
            os.replace(tmp, path)
        except OSError:
            pass

    def _take_spool_entries(self) -> list[str]:
        """Claim (unlink) and return every spooled key."""
        try:
            names = os.listdir(self._spool_dir)
        except FileNotFoundError:
            return []
        keys = []
        for fn in sorted(names):
            if fn.endswith(_TMP_SUFFIX):
                continue
            try:
                os.unlink(os.path.join(self._spool_dir, fn))
            except OSError:
                continue  # another claimant got it first
            keys.append(unquote(fn))
        return keys

    def _drain_spool(self) -> int:
        keys = self._take_spool_entries()
        for key in keys:
            self.submit(key)
        return len(keys)

    def drain(self) -> None:
        """Final flush: process every pending + scannable file, then return.
        Called at application shutdown ('materialize onto long-term
        storage'). Correct under the worker pool: waits on an explicit
        queued+in-flight count rather than poking at the queue's private
        ``unfinished_tasks`` outside its mutex. A follower instead hands
        its keys to the leader and waits for the spool to empty.

        Durability contract: a flush that still fails by the end of the
        drain RAISES to the caller (the seed surfaced this through its
        dying worker's exception) — shutdown must never silently report
        success while a file never reached long-term storage."""
        self._draining = True
        try:
            self._drain_inner()
            self._raise_failed_sync()
        finally:
            self._draining = False

    def _raise_failed_sync(self) -> None:
        """Final synchronous pass over flushes that failed during the
        drain: transient blips heal here; a persistent error propagates
        (``process`` has no handler) so the caller knows durability was
        not achieved."""
        with self._cv:
            failed = sorted(self._failed)
            self._failed.clear()
        for key in failed:
            self.process(key)

    def _drain_inner(self) -> None:
        self.scan()
        if self._coordinated and not self.is_leader:
            if not self._drain_as_follower():
                return
            # became leader mid-drain: fall through and drain like one
        if not self._alive():
            # synchronous fallback: no daemon running
            self._process_all_sync()
            return
        stable = 0
        while True:
            if self._coordinated and self.is_leader:
                self._drain_spool()  # followers may still be handing us work
            with self._cv:
                while self._pending or self._inflight:
                    if not self._alive():
                        break
                    self._cv.wait(timeout=0.5)
            if not self._alive():
                self._process_all_sync()
                return
            if not (self._coordinated and self.is_leader):
                return
            # leader: only finish once spool AND queue are empty twice in a
            # row — a follower's entry can be mid-claim (unlinked by the
            # coordinator thread but not yet queued) at any single glance
            if self._spool_empty() and not self._pending and not self._inflight:
                stable += 1
                if stable >= 2:
                    return
                time.sleep(0.01)
            else:
                stable = 0

    def _spool_empty(self) -> bool:
        try:
            names = os.listdir(self._spool_dir)
        except FileNotFoundError:
            return True
        return all(n.endswith(_TMP_SUFFIX) for n in names)

    def _drain_as_follower(self) -> bool:
        """Wait until the leader drained the spool. Returns True iff this
        instance took leadership over (caller then drains as the leader).
        If no live leader materializes before the deadline, the leftovers
        are processed synchronously — data safety over single-flusher
        purity at shutdown."""
        deadline = time.time() + max(5.0, 10 * self._hb_interval)
        while time.time() < deadline:
            try:
                entries = [
                    n
                    for n in os.listdir(self._spool_dir)
                    if not n.endswith(_TMP_SUFFIX)
                ]
            except FileNotFoundError:
                entries = []
            if not entries:
                return False
            if self._heartbeat_stale() and self._try_acquire_leadership():
                return True
            time.sleep(min(0.05, self._hb_interval / 4))
        for key in self._take_spool_entries():
            self.process(key)
        return False

    # -- event plumbing --------------------------------------------------------
    def _on_close(self, key: str, writing: bool) -> None:
        with self._cv:
            deferred = key in self._deferred
            self._deferred.discard(key)
        if writing or deferred:
            # a read close matters too when a reader held the file busy
            # during an earlier flush attempt
            self.submit(key)

    def submit(self, key: str) -> None:
        if self._coordinated and not self.is_leader:
            self._spool_submit(key)
            return
        stamp = time.perf_counter_ns() if self.fs.telemetry.spans_on else 0
        with self._cv:
            if key in self._active:
                # a worker is processing this key right now: flag it for
                # one more pass instead of dropping the event (the file
                # may have been rewritten under the in-flight flush)
                self._active[key] = True
                return
            if key in self._pending:
                return
            self._pending[key] = stamp
        self._q.put(key)

    def scan(self) -> int:
        """Stateless sweep of cache tiers for files needing flush/evict."""
        n = 0
        for tier in self.fs.hierarchy.cache_tiers:
            for root in tier.roots:
                for dirpath, dirs, files in os.walk(root):
                    if LEDGER_DIRNAME in dirs:
                        dirs.remove(LEDGER_DIRNAME)
                    for fn in files:
                        if fn.endswith(_TMP_SUFFIX):
                            # in-flight staging files are not keys; dead
                            # ones (crashed transfers) are reclaimed here
                            self.fs.transfer.maybe_reap_orphan(
                                os.path.join(dirpath, fn)
                            )
                            continue
                        if fn.endswith(PART_SUFFIX):
                            # partial extent replicas are never flush
                            # candidates: their base copy already exists
                            continue
                        key = os.path.relpath(os.path.join(dirpath, fn), root)
                        if self.fs.rules.mode(key) is not Mode.KEEP:
                            self.submit(key)
                            n += 1
        return n

    # -- workers ------------------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                key = self._q.get(timeout=self.config.flush_interval_s)
            except queue.Empty:
                self._maybe_retry_failed()
                continue
            if key is None:
                if self._stop.is_set():
                    break
                continue  # stale sentinel from a previous stop()
            with self._cv:
                stamp = self._pending.pop(key, 0)
                self._active[key] = False
                self._inflight += 1
            try:
                try:
                    self.process(key, queued_ns=_since(stamp))
                except Exception as e:
                    # a failed flush (exhausted transfer retries, device
                    # error) must not kill the worker thread — but it
                    # must not vanish either: count it, surface the
                    # traceback, and queue the key for a retry on the
                    # next idle tick (drain()/shutdown also re-scan)
                    self.fs.telemetry.record_flush_failure()
                    traceback.print_exc()
                    # backoff: a persistently failing key re-copies (and
                    # tracebacks) at most ~once per second, not once per
                    # idle tick. The shared errno table (repro.core.faults)
                    # stretches it 30x for permanent/capacity classes —
                    # EACCES or a full base tier will not heal in a second,
                    # and cache-root ENOSPC already tripped the breaker
                    # inside the engine instead of burning retries here.
                    backoff = max(1.0, 10 * self.config.flush_interval_s)
                    if classify(e) is not TRANSIENT:
                        backoff *= 30
                    with self._cv:
                        self._failed[key] = time.monotonic() + backoff
            finally:
                requeue = False
                with self._cv:
                    if self._active.pop(key, False):
                        # a submit arrived mid-process: queue one more pass
                        self._pending[key] = 0
                        requeue = True
                    self._inflight -= 1
                    self._cv.notify_all()
                if requeue:
                    self._q.put(key)
                # re-check after every task as well as on idle ticks: a
                # sustained submit stream never leaves the queue empty,
                # and a failed key must still get its backed-off retry
                self._maybe_retry_failed()

    def _maybe_retry_failed(self) -> None:
        """Re-submit every failed flush whose backoff has elapsed (the
        engine's own retry/backoff absorbed the fast transients; this
        covers longer outages). The whole eligible backlog goes in one
        tick: after a mass failure — a tier dying and recovering — the
        old one-key-per-idle-tick behaviour drained N keys in
        N*flush_interval_s instead of letting the worker pool chew them
        concurrently. Suspended during drain() — a permanently failing
        key must not keep the pending set non-empty forever."""
        retries: list[str] = []
        with self._cv:
            if not self._draining:
                now = time.monotonic()
                retries = [k for k, nb in self._failed.items() if nb <= now]
                for k in retries:
                    del self._failed[k]
        for k in retries:
            self.submit(k)

    def _process_all_sync(self) -> None:
        while True:
            try:
                key = self._q.get_nowait()
            except queue.Empty:
                return
            if key is None:
                continue
            with self._cv:
                stamp = self._pending.pop(key, 0)
                self._active[key] = False
            self.process(key, queued_ns=_since(stamp))
            requeue = False
            with self._cv:
                if self._active.pop(key, False):
                    self._pending[key] = 0
                    requeue = True
            if requeue:
                self._q.put(key)

    # -- the four modes ------------------------------------------------------------
    def process(self, key: str, queued_ns: int = 0) -> Mode:
        """Flush and/or evict ``key`` as its mode says. ``queued_ns``: how
        long it waited in the queue, recorded as ``flush.queued`` if it
        moves (0: not measured)."""
        mode = self.fs.rules.mode(key)
        if mode is Mode.KEEP:
            return mode
        with self.fs.key_lock(key):
            if self.fs.open_count(key):
                # busy: never move a file underneath the application (paper
                # §5.5 limitation). Defer to the NEXT close of this key —
                # an immediate requeue would busy-spin while it stays open.
                with self._cv:
                    self._deferred.add(key)
                return mode
            # ignore_negative: a spooled key from another process may never
            # have been seen locally — a negative entry must not hide it
            located = self.fs.resolver.resolve(key, ignore_negative=True)
            if located is None:
                return mode
            tier, real = located
            if tier.persistent:
                return mode  # already on long-term storage: nothing to do
            telemetry = self.fs.telemetry
            if queued_ns:
                telemetry.record_span("flush.queued", queued_ns / 1e9)
            with telemetry.span("flush.move") as span:
                if mode in (Mode.COPY, Mode.MOVE):
                    span.nbytes = self._flush_one(key, real, tier)
                if mode in (Mode.MOVE, Mode.REMOVE):
                    if not self._draining and self.fs.prefetcher.is_hot(key):
                        # predicted-hot: the readahead engine staged (or is
                        # staging) this key because the application is about
                        # to read it — evicting now would throw that work
                        # away. The flush above still ran; the evict retries
                        # on an idle tick once the hotness expires. drain()
                        # ignores hotness: shutdown durability wins.
                        with self._cv:
                            self._failed.setdefault(
                                key, time.monotonic() + 2 * self._hb_interval
                            )
                        return mode
                    span.nbytes = self._evict_one(key, real, tier) or span.nbytes
        return mode

    def _flush_one(self, key: str, src: str, src_tier=None) -> int:
        """Copy ``key`` to the base tier; returns the bytes copied (0: the
        base copy was already fresh, or the source vanished)."""
        base = self.fs.hierarchy.base
        base_root = base.roots[0]
        dst = os.path.join(base_root, key)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        try:
            sst = os.stat(src)
        except OSError:
            return 0  # vanished under the key lock's last release: nothing to do
        try:
            dst_st = os.stat(dst)
        except OSError:
            dst_st = None
        if (
            dst_st is not None
            and dst_st.st_mtime_ns >= sst.st_mtime_ns
            and dst_st.st_size == sst.st_size
        ):
            # already materialized and fresh. Nanosecond mtimes + size:
            # a coarse same-second getmtime() compare silently skipped
            # sources rewritten within one mtime tick of the last flush.
            # The engine copystats the source onto the committed copy, so
            # equality here means byte-for-byte freshness.
            return 0
        # the flusher only ever drains *away from* cache roots: the
        # destination is always the base tier, which the breaker never
        # quarantines — a sick root's files still reach durability while
        # nothing new is staged into it (placement filters it out)
        faults.fire("flusher.flush", path=src)
        result = self.fs.transfer.copy(
            src,
            dst,
            src_tier=src_tier,
            dst_tier=base,
            dst_root=base_root,
            key=key,
            admit="reserve",
        )
        self.fs.telemetry.record_flush(result.nbytes)
        return result.nbytes

    def _evict_one(self, key: str, src: str, tier) -> int:
        """Remove the cache copy of ``key``; returns its bytes (0: failed)."""
        try:
            nbytes = os.path.getsize(src)
            os.remove(src)
            root = tier.root_of(src)
            if root is not None:
                tier.note_removed(root, key)
            # one invalidation covers the move: the next resolve re-scans
            # and lands on the base copy (or nothing, for REMOVE mode)
            self.fs.resolver.invalidate(key)
            self.fs._fed_unpublish(key)
            self.fs.telemetry.record_evict(nbytes)
            return nbytes
        except OSError:
            return 0

    # -- prefetch -----------------------------------------------------------------
    def prefetch(self) -> int:
        """Stage .sea_prefetchlist matches from the base tier into the
        fastest cache tier with room ("For files to be prefetched, they
        must be located within Sea's mountpoint at startup").

        Candidates are collected in one walk, then staged through the
        transfer engine's bounded worker pool — independent copies
        overlap (``transfer_workers`` at a time), which is where the
        wall-clock win over the seed's serial loop lives."""
        base = self.fs.hierarchy.base
        candidates: list[str] = []
        seen: set[str] = set()  # multi-root base: one stage per key
        for root in base.roots:
            for dirpath, dirs, files in os.walk(root):
                if LEDGER_DIRNAME in dirs:
                    dirs.remove(LEDGER_DIRNAME)
                for fn in files:
                    real = os.path.join(dirpath, fn)
                    if fn.endswith(_TMP_SUFFIX):
                        # half-written staging files are not prefetchable
                        # keys; reclaim provably-dead ones
                        self.fs.transfer.maybe_reap_orphan(real)
                        continue
                    if fn.endswith(PART_SUFFIX):
                        continue  # extent plane bookkeeping, not a key
                    key = os.path.relpath(real, root)
                    if key not in seen and self.fs.rules.prefetch_match(key):
                        seen.add(key)
                        candidates.append(key)
        if not candidates:
            return 0
        # SeaFS.stage_to_cache holds the key lock on a transfer worker, so
        # staging stays atomic against evicts/flushes of the same key and
        # shares one code path with the data pipeline
        return sum(self.fs.transfer.map(self.fs.stage_to_cache, candidates))


class Sea:
    """Top-level convenience bundle: SeaFS + running Flusher pool.

    >>> sea = Sea(config).start()
    >>> with sea.fs.open(f"{config.mount}/x.bin", "wb") as f: ...
    >>> sea.shutdown()      # drain & stop (final flush)
    """

    def __init__(self, config):
        self.fs = SeaFS(config)
        self.flusher = Flusher(self.fs)
        self._started = False

    def start(self) -> "Sea":
        if self._started:
            return self  # idempotent: a second start must not re-prefetch
        self.flusher.start()
        if self.fs.config.prefetchlist:
            self.flusher.prefetch()
        self._started = True
        return self

    def shutdown(self) -> None:
        try:
            # stop speculative readahead first: pending predictions are
            # cancelled and counted, and no new staging races the drain
            self.fs.prefetcher.stop()
            # drain may RAISE when a flush never succeeded (durability
            # contract) — leadership and workers must still be released
            try:
                self.flusher.drain()
            finally:
                self.flusher.stop()
        finally:
            self._started = False
            # stop the transfer pool too (it restarts lazily if reused)
            self.fs.transfer.close()
        if self.fs.federation is not None:
            # leave the cluster cleanly: nobody maintains our registry
            # entries once this process exits, so drop them now instead
            # of making peers burn a failed pull + TTL expiry on them
            try:
                self.fs.federation.retire()
            except OSError:
                pass
        if self.fs.config.shared_ledger:
            # leave this process's counters next to the shared store so the
            # workflow can aggregate telemetry across all its workers
            stats_dir = os.path.join(
                self.fs.hierarchy.base.roots[0], LEDGER_DIRNAME, "telemetry"
            )
            try:
                self.fs.telemetry.export(
                    os.path.join(stats_dir, f"{os.getpid()}.json")
                )
            except OSError:
                pass

    def __enter__(self) -> "Sea":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()
