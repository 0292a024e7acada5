"""SeaFS — stateless path translation + file operations over the hierarchy.

This is the Python-level equivalent of the paper's glibc wrappers: "The
wrappers take any input filepath that is located within the user-provided
Sea mountpoint and convert it to a filepath pointing to the best available
storage device." Every operation resolves mount-relative keys against the
tier hierarchy at call time; the file systems themselves are the only state
(decentralized/stateless, per the paper's design vs. BurstFS/GekkoFS).
"""

from __future__ import annotations

import errno
import io
import os
import shutil as _shutil
import stat as stat_mod
import threading
import time
from collections import defaultdict

from . import faults
from .config import SeaConfig
from .extents import PART_SUFFIX, ExtentStore, extent_token, punch_hole
from .faults import CAPACITY, FaultPlane, classify
from .federation import FederationRegistry
from .health import HealthTracker
from .ledger import LEDGER_DIRNAME, TMP_SUFFIX, file_disk_usage
from .lists import CompiledRules, Mode
from .placement import PlacementPolicy
from .prefetcher import Prefetcher
from .resolver import Resolver
from .telemetry import Telemetry
from .tiers import Hierarchy, Tier
from .transfer import TransferEngine

_WRITE_CHARS = ("w", "a", "x", "+")
_STRIPE_MANIFEST_SUFFIX = ".sea_stripe.json"
_TMP_SUFFIX = TMP_SUFFIX  # atomic-commit staging (one canonical suffix)

# bound at import time: SeaFS's own truncate paths must reach the real
# syscalls even while a SeaMount context has os.truncate/os.ftruncate
# patched (the wrappers route mount paths back here — recursion otherwise)
_os_truncate = os.truncate
_os_ftruncate = os.ftruncate


def _is_write_mode(mode: str) -> bool:
    return any(c in mode for c in _WRITE_CHARS)


def _spanned(span, fn, *args):
    """``fn(*args)`` inside ``span``; the span's bytes are the returned
    count or the length of the returned data."""
    with span:
        out = fn(*args)
        span.nbytes = out if isinstance(out, int) else len(out or b"")
    return out


class _SeaFile:
    """Proxy around a real file object: forwards everything, and notifies
    SeaFS on close so the flush-and-evict daemon can pick the file up.
    ``read``/``readinto``/``readline`` and ``write`` are real methods, so
    that each call is a ``sea.read`` or ``sea.write`` span while spans are
    on (one attribute check while they are off).
    Open files are refcounted — the flusher never moves a busy file
    (beyond-paper fix for the paper's §5.5 known limitation). A write
    handle additionally carries its capacity reservation, committed (with
    the actual on-disk size) when the file closes."""

    def __init__(
        self,
        fs: "SeaFS",
        key: str,
        raw,
        tier: Tier,
        writing: bool,
        real: str,
        reservation=None,
        fast: bool = False,
    ):
        self._fs = fs
        self._key = key
        self._raw = raw
        self._tier = tier
        self._writing = writing
        self._real = real
        self._reservation = reservation
        self._fast = fast
        self._tel = fs.telemetry
        # open-to-close time of a write handle: the health evidence its
        # commit records
        self._t0 = time.perf_counter() if writing else 0.0
        self._closed = False
        self._fd = None
        if writing:
            # register the fd so an os.ftruncate against this handle can
            # be routed back through SeaFS for ledger/extent settlement
            try:
                self._fd = raw.fileno()
            except (OSError, ValueError, AttributeError):
                self._fd = None
            if self._fd is not None:
                fs._fd_index[self._fd] = (key, tier, real)

    @property
    def sea_tier(self) -> str:
        """Name of the tier this handle was opened against (benchmarks
        and tools use this to see where a read was actually served)."""
        return self._tier.name

    def __getattr__(self, name):
        return getattr(self._raw, name)

    def read(self, *args):
        if self._tel.spans_on and not self._writing:
            return _spanned(self._tel.span("sea.read"), self._raw.read, *args)
        return self._raw.read(*args)

    def readinto(self, b):
        if self._tel.spans_on and not self._writing:
            return _spanned(self._tel.span("sea.read"), self._raw.readinto, b)
        return self._raw.readinto(b)

    def readline(self, *args):
        if self._tel.spans_on and not self._writing:
            return _spanned(self._tel.span("sea.read"), self._raw.readline, *args)
        return self._raw.readline(*args)

    def write(self, data):
        if not self._writing:
            return self._raw.write(data)
        if self._tel.spans_on:
            return _spanned(self._tel.span("sea.write"), self._write, data)
        return self._write(data)

    def _write(self, data):
        raw = self._raw
        pre_pos = None
        if not self._tier.spec.persistent:
            # logical position before the write: a large buffered write
            # goes straight to the raw fd, so ENOSPC can strike after a
            # prefix of `data` already landed — post-failure tell() counts
            # those bytes, and relocation trusting it would carry the
            # prefix over AND rewrite the full data after it (silent
            # duplication). Migration must rewind to here instead.
            try:
                pre_pos = raw.tell()
            except (OSError, ValueError):
                pre_pos = None
        try:
            faults.fire("seafs.write", path=self._real)
            return raw.write(data)
        except OSError as e:
            if (
                self._tier.spec.persistent
                or classify(e) != CAPACITY
                or pre_pos is None
            ):
                raise
            # the cache root filled mid-stream: migrate the half-written
            # handle to the next eligible root (or base) and keep going
            return self._fs._relocate_write(self, data, e, pre_pos)

    def __iter__(self):
        return iter(self._raw)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._fd is not None:
            self._fs._fd_index.pop(self._fd, None)
        try:
            try:
                pos = self._raw.tell()
            except (OSError, ValueError):
                pos = 0
            self._raw.close()
        finally:
            dt = time.perf_counter() - self._t0 if self._writing else 0.0
            self._fs._on_close(
                self._key,
                self._tier,
                self._writing,
                pos,
                dt,
                self._real,
                self._reservation,
                self._fast,
            )

    @property
    def closed(self):
        return self._raw.closed

    def __repr__(self):  # pragma: no cover
        return f"<SeaFile key={self._key!r} tier={self._tier.name}>"


class _ExtentRaw(io.RawIOBase):
    """Raw composite reader of the extent plane (``SeaFS.open`` wraps it
    in a :class:`io.BufferedReader`): staged extents are served with a
    ``pread`` of the sparse cache part file; a missing extent is faulted
    synchronously through the transfer engine on first touch (O(1 extent)
    time-to-first-byte) and served from cache; when staging is refused
    (no room, I/O error) the bytes stream straight from the base replica
    — the reader never waits on more than one extent and never fails
    because the cache is full. Every first touch of a new extent also
    feeds the within-file readahead predictor, so sequential scans find
    the next extents already staged.

    Hit reads take the map's lock around the validity check + ``pread``
    pair, which excludes the punch-hole eviction path — a reader can see
    an extent either fully staged or invalid, never a half-punched hole.
    Concurrent-overwrite semantics match POSIX reads of a file being
    rewritten: torn, but never blocking."""

    def __init__(self, fs: "SeaFS", key: str, em, base_real: str, base_tier):
        super().__init__()
        self._fs = fs
        self._key = key
        self._em = em
        self._base_real = base_real
        self._base_tier = base_tier
        self._size = em.size
        self._pos = 0
        self._part_fd = os.open(em.part_real, os.O_RDONLY)
        self._base_fd = -1  # lazy: an all-hit stream never opens the base
        self._last_idx = -1

    def readable(self) -> bool:
        return True

    def seekable(self) -> bool:
        return True

    def seek(self, offset: int, whence: int = os.SEEK_SET) -> int:
        if whence == os.SEEK_SET:
            pos = offset
        elif whence == os.SEEK_CUR:
            pos = self._pos + offset
        elif whence == os.SEEK_END:
            pos = self._size + offset
        else:
            raise ValueError(f"invalid whence: {whence}")
        if pos < 0:
            raise OSError(errno.EINVAL, "negative seek position")
        self._pos = pos
        return pos

    def tell(self) -> int:
        return self._pos

    def _base(self) -> int:
        if self._base_fd < 0:
            self._base_fd = os.open(self._base_real, os.O_RDONLY)
        return self._base_fd

    def readinto(self, b) -> int:
        if self._pos >= self._size:
            return 0
        fs, em = self._fs, self._em
        idx = em.index_of(self._pos)
        start, length = em.extent_range(idx)
        # serve within one extent per call (RawIOBase short reads are the
        # contract; BufferedReader re-calls across the boundary)
        want = min(len(b), start + length - self._pos)
        if want <= 0:  # zero-length destination buffer
            return 0
        if idx != self._last_idx:
            self._last_idx = idx
            fs.prefetcher.observe_extent(self._key, idx)
        data = None
        hit = False
        with em.lock:
            if em.is_valid(idx):
                data = os.pread(self._part_fd, want, self._pos)
                hit = True
        if not hit:
            if fs._fault_extent(em, idx):
                with em.lock:
                    if em.is_valid(idx):
                        data = os.pread(self._part_fd, want, self._pos)
            if data is None:
                data = os.pread(self._base(), want, self._pos)
        n = len(data)
        b[:n] = data
        self._pos += n
        em.touch(idx)
        fs.telemetry.record_extent_read(hit=hit, nbytes=n)
        return n

    def close(self) -> None:
        if not self.closed:
            try:
                os.close(self._part_fd)
                if self._base_fd >= 0:
                    os.close(self._base_fd)
            except OSError:
                pass
        super().close()


class SeaFS:
    """One Sea instance (one per node, as in the paper)."""

    def __init__(self, config: SeaConfig, *, telemetry: Telemetry | None = None):
        self.config = config
        self.hierarchy: Hierarchy = config.build_hierarchy()
        self.telemetry = telemetry or Telemetry()
        self.hierarchy.ledger.telemetry = self.telemetry
        # failure-domain layer: per-root sliding-window health feeding a
        # circuit breaker; quarantined cache roots drop out of placement
        # until a half-open probe succeeds (the base tier is never gated)
        self.health = HealthTracker(
            window_s=config.health_window_s,
            error_threshold=config.health_error_threshold,
            min_events=config.health_min_events,
            open_s=config.health_open_s,
            telemetry=self.telemetry,
        )
        self.policy = PlacementPolicy(
            self.hierarchy,
            max_file_size=config.max_file_size,
            n_procs=config.n_procs,
            health=self.health,
        )
        self.resolver = Resolver(
            self.hierarchy,
            self.telemetry,
            negative_ttl_s=config.resolver_negative_ttl_s,
            verify_window_s=config.resolver_verify_window_s,
        )
        self.rules = CompiledRules(
            config.flushlist, config.evictlist, config.prefetchlist
        )
        # the data plane: every tier-to-tier byte moves through here
        self.transfer = TransferEngine(config, self.telemetry, self.policy)
        self.transfer.health = self.health
        # fault-injection plane (tests/chaos benches only): activates the
        # process-wide plane from the config spec string
        if getattr(config, "faults", ""):
            faults.activate(
                FaultPlane.from_spec(config.faults, seed=config.fault_seed)
            )
        self.mount = config.mount
        os.makedirs(self.mount, exist_ok=True)
        self._mount_prefix = self.mount + os.sep
        self._open_counts: dict[str, int] = defaultdict(int)
        self._open_writers: dict[str, int] = {}  # keys open for write
        self._lock = threading.RLock()
        self._key_locks: dict[str, threading.RLock] = {}
        self._close_listeners: list = []  # flusher subscribes here
        self._access_clock: dict[str, float] = {}  # LRU bookkeeping (opt-in)
        self._readahead = bool(getattr(config, "readahead", False))
        # extent-granular data plane (opt-in): partial sparse replicas on
        # cache tiers, per-extent staging/eviction, streaming reads
        self.extents: ExtentStore | None = (
            ExtentStore(config.extent_bytes, self.telemetry)
            if getattr(config, "extent_map", False)
            else None
        )
        self.resolver.extent_store = self.extents
        # cluster-scale cache federation (opt-in): publish cache replicas
        # to the shared registry on the base tier and pull peer->cache on
        # a local miss (third resolution tier: local -> peer -> base)
        self.federation: FederationRegistry | None = (
            FederationRegistry(
                self.hierarchy.base.roots[0],
                config.federation_node or None,
                heartbeat_s=config.federation_heartbeat_s,
                node_ttl_s=config.federation_node_ttl_s,
                telemetry=self.telemetry,
            )
            if getattr(config, "federation", False)
            else None
        )
        self.resolver.federation = self.federation
        #: fd -> (key, tier, real) of open Sea write handles, so the
        #: ftruncate intercept can settle accounting for fd-only calls
        self._fd_index: dict[int, tuple[str, Tier, str]] = {}
        # predictive readahead (observes read opens, stages speculatively
        # through the transfer pool); inert unless config.readahead
        self.prefetcher = Prefetcher(self)

    # -- path plumbing -------------------------------------------------------
    def is_sea_path(self, path: str) -> bool:
        ap = os.path.abspath(path)
        return ap == self.mount or ap.startswith(self._mount_prefix)

    def fast_path_class(self, path) -> bool | None:
        """One-``startswith`` mount classification for already-normalized
        absolute strings: True = definitively under the mount, False =
        definitively outside, None = undecided (relative, non-``str``,
        or containing ``//``/dot components that normalization could
        collapse — run the ``abspath`` probe). The single source of this
        heuristic: ``SeaFS.open``'s fast path and the ``SeaMount``
        wrappers both classify through here, so they can never drift."""
        if (
            path.__class__ is not str
            or not path.startswith(os.sep)
            or "/." in path
            or "//" in path
            or path.endswith(os.sep)
        ):
            return None
        if path.startswith(self._mount_prefix) or path == self.mount:
            return True
        return False

    def _fast_key(self, path) -> str | None:
        """Mount-relative key when ``path`` is an already-normalized
        absolute string strictly under the mount; None = undecided or
        not a plain key (the caller takes the abspath-based slow path,
        so a miss here is a de-opt, never a misroute)."""
        if self.fast_path_class(path) is True and path != self.mount:
            return path[len(self._mount_prefix) :]
        return None

    def key_of(self, path: str) -> str:
        """Mount-relative key of a path under the mountpoint."""
        return os.path.relpath(os.path.abspath(path), self.mount)

    def key_lock(self, key: str) -> threading.RLock:
        with self._lock:
            lk = self._key_locks.get(key)
            if lk is None:
                lk = self._key_locks[key] = threading.RLock()
            return lk

    def open_count(self, key: str) -> int:
        with self._lock:
            return self._open_counts.get(key, 0)

    def add_close_listener(self, fn) -> None:
        self._close_listeners.append(fn)

    # -- resolution ----------------------------------------------------------
    def resolve_read(self, key: str) -> tuple[Tier, str] | None:
        """Locate an existing file, fastest tier first — a pure dict
        lookup within the verify trust window, one verify ``lstat`` past
        it, the full probe cascade only on a cold/invalidated key.
        Callers that open the returned path should treat ENOENT as a
        failed verify and re-resolve (``SeaFS.open`` does)."""
        with self.key_lock(key):
            return self.resolver.resolve(key, trust_window=True)

    def resolve_write(self, key: str) -> tuple[Tier, str]:
        """Pick the destination for a (re)write.

        If the file already exists somewhere, overwrite in place (the
        hierarchy must never hold two divergent copies); otherwise select
        the fastest tier with space.
        """
        tier, real, res = self._resolve_write(key, reserve=False)
        assert res is None
        return tier, real

    def _resolve_write(
        self, key: str, *, reserve: bool
    ) -> tuple[Tier, str, object | None]:
        """``resolve_write`` plus (optionally) an atomic admission: the
        eligibility re-check and the in-flight reservation happen in one
        critical section per root, and a lost race re-selects — so
        concurrent writers of *different* keys can never jointly
        over-commit a capped root."""
        with self.key_lock(key):
            # check_faster: an overwrite must land on the TRUE fastest
            # replica, so a cached hit additionally probes the tiers above
            # it (free when the hit is already on tier 0)
            found = self.resolver.resolve(key, check_faster=True)
            if found is not None:
                tier, real = found
                res = None
                if reserve:
                    root = tier.root_of(real)
                    if root is not None:
                        # overwrite in place: no admission, just hold the
                        # in-flight budget until close commits the size
                        with self.telemetry.span("sea.admit"):
                            res = self.policy.reserve_write(tier, root)
                return tier, real, res
            make_room = self._lru_make_room if self.config.lru_evict else None
            with self.telemetry.span("sea.admit"):
                tier, root, res = self.policy.place_new(
                    reserve=reserve, make_room=make_room
                )
            real = os.path.join(root, key)
            os.makedirs(os.path.dirname(real), exist_ok=True)
            # verified=False: the file is not materialized until the
            # caller's io.open — the first read hit must verify
            self.resolver.note_location(key, tier, real, verified=False)
            return tier, real, res

    def resolve(self, path: str, mode: str = "r") -> str:
        """Public path-translation API (for tools that want the real path
        without going through ``open``)."""
        if not self.is_sea_path(path):
            return path
        key = self.key_of(path)
        if _is_write_mode(mode):
            return self.resolve_write(key)[1]
        found = self.resolve_read(key)
        if found is not None:
            return found[1]
        # Not found anywhere: report the base-tier path so the caller gets
        # POSIX ENOENT semantics against the persistent location.
        return os.path.join(self.hierarchy.base.roots[0], key)

    # -- file operations ------------------------------------------------------
    def open(self, path: str, mode: str = "r", **kw):
        writing = _is_write_mode(mode)
        if self.telemetry.spans_on:
            with self.telemetry.span("sea.open.write" if writing else "sea.open.read"):
                return self._open(path, mode, writing, kw)
        return self._open(path, mode, writing, kw)

    def _open(self, path: str, mode: str, writing: bool, kw: dict):
        if not writing:
            f = self._open_read_fast(path, mode, kw)
            if f is not None:
                return f
        if not self.is_sea_path(path):
            self.telemetry.record_redirect(False)
            return io.open(path, mode, **kw)
        self.telemetry.record_redirect(True)
        key = self.key_of(path)
        if self._readahead and not writing:
            self.prefetcher.observe(key)
        with self.key_lock(key):
            reservation = None
            if writing:
                tier, real, reservation = self._resolve_write(key, reserve=True)
                # register the writer BEFORE the (truncating) io.open so
                # read fast paths divert to the key-locked slow path for
                # the whole write, not just after the open returns
                with self._lock:
                    self._open_writers[key] = self._open_writers.get(key, 0) + 1
                # a partial extent replica of the old content is stale the
                # moment a writer opens the key
                self._discard_extents(key)
            else:
                found = self.resolve_read(key)
                if found is None:
                    # a fresh negative entry may hide a file another
                    # process created moments ago: one authoritative
                    # scan before declaring the miss — open() must never
                    # spuriously fail because of the cache
                    found = self.resolver.resolve(key, ignore_negative=True)
                if self.federation is not None and (
                    found is None or found[0].persistent
                ):
                    # third resolution tier: a key staged on a live peer
                    # is pulled peer->cache instead of read cold from base
                    pulled = self._pull_from_peer(key)
                    if pulled is not None:
                        found = pulled
                if found is None:
                    return self._open_base_miss(key, mode, **kw)
                tier, real = found
                if (
                    self.extents is not None
                    and tier.persistent
                    and "b" in mode
                    and not kw
                ):
                    f = self._open_extent_read(key, tier, real)
                    if f is not None:
                        return f
            try:
                if not writing:
                    faults.fire("seafs.open", path=real)
                raw = io.open(real, mode, **kw)
            except FileNotFoundError:
                if reservation is not None:
                    self.policy.release_write(tier, reservation)
                if writing:
                    self._drop_writer(key)
                    raise
                # the open doubled as the verify and failed (the file
                # moved between resolution and open): heal and retry once
                found = self.resolver.refresh(key)
                if found is None:
                    return self._open_base_miss(key, mode, **kw)
                tier, real = found
                try:
                    raw = io.open(real, mode, **kw)
                except FileNotFoundError:
                    # removed again mid-retry: raise the canonical error
                    # against the persistent location, like a plain miss
                    return self._open_base_miss(key, mode, **kw)
            except OSError as e:
                if reservation is not None:
                    self.policy.release_write(tier, reservation)
                if writing:
                    self._drop_writer(key)
                    raise
                if tier.persistent:
                    raise  # the base is the last resort; nothing slower
                # a real I/O error from a cache device (EIO, dead mount):
                # feed the breaker and degrade to any other replica
                return self._open_read_degraded(key, mode, kw, tier, real, e)
            except Exception:
                if reservation is not None:
                    self.policy.release_write(tier, reservation)
                if writing:
                    self._drop_writer(key)
                raise
            with self._lock:
                self._open_counts[key] += 1
                self._access_clock[key] = time.monotonic()
        return _SeaFile(self, key, raw, tier, writing, real, reservation)

    def _drop_writer(self, key: str) -> None:
        with self._lock:
            n = self._open_writers.get(key, 0) - 1
            if n <= 0:
                self._open_writers.pop(key, None)
            else:
                self._open_writers[key] = n

    def _open_read_fast(self, path, mode: str, kw):
        """Read-hit fast path: a single lock-free resolver lookup, the
        ``io.open`` itself, and one counts update — no key lock, no
        telemetry mutex (per-thread batched counters), no ``abspath``.

        Correctness: served only for (a) normalized absolute paths under
        the mount, (b) keys with **no registered writer** (writers
        register before their truncating open, re-checked after ours),
        and (c) resolver entries inside the verify trust window. The
        ``io.open`` doubles as the verify — any failure returns None and
        the caller re-runs the full key-locked slow path, which heals
        moved files and settles races. A fast hit therefore observes
        either a complete committed file or nothing (the atomic-commit
        invariant of the data plane); it can never see a mid-flush move
        as a partial file or a spurious miss."""
        key = self._fast_key(path)
        if not key:
            return None
        if self._open_writers.get(key):
            return None
        found = self.resolver.resolve_fast(key)
        if found is None:
            return None
        tier, real = found
        if self.extents is not None and tier.persistent:
            # a base-resolved read may belong to the extent plane (partial
            # replica, streaming fault-in): always route through the
            # key-locked slow path, which owns that decision
            return None
        try:
            faults.fire("seafs.open", path=real)
            raw = io.open(real, mode, **kw)
        except OSError:
            return None  # the open doubled as the verify: slow path heals
        if self._open_writers.get(key):
            # a writer registered between the check and the open: drop
            # the handle and serialize through the key-locked slow path
            raw.close()
            return None
        with self._lock:
            self._open_counts[key] += 1
            self._access_clock[key] = time.monotonic()
        lc = self.telemetry.local()
        lc.redirect_hits += 1
        lc.fastpath_opens += 1
        if self._readahead:
            self.prefetcher.observe(key)
        return _SeaFile(self, key, raw, tier, False, real, fast=True)

    def _open_base_miss(self, key: str, mode: str, **kw):
        """The canonical miss: open against the persistent location so the
        caller gets POSIX ENOENT semantics (or creates the file there,
        for write modes reaching this fallback)."""
        return io.open(
            os.path.join(self.hierarchy.base.roots[0], key), mode, **kw
        )

    def _open_read_degraded(self, key: str, mode: str, kw, tier, real, exc):
        """A cache-tier read open failed with a genuine I/O error (not
        ENOENT). Called under the key lock. Feed the root's breaker, then
        serve the read from any OTHER replica — another root or tier, a
        live peer, or the base copy — so a sick device degrades service
        instead of failing the application. Re-raises the original error
        only when no healthy replica exists anywhere (a cache-only key
        whose sole copy sits on the dead root is genuinely lost)."""
        root = tier.root_of(real)
        if root is not None:
            self.health.record_failure(root, exc)
        bad = os.path.abspath(real)
        self.resolver.invalidate(key)
        for vtier, vreal in self.hierarchy.locate_all(key):
            if os.path.abspath(vreal) == bad:
                continue
            if not vtier.persistent:
                vroot = vtier.root_of(vreal)
                if vroot is not None and self.health.quarantined(vroot):
                    continue
            try:
                raw = io.open(vreal, mode, **kw)
            except OSError:
                continue
            self.telemetry.record_degraded_read()
            self.resolver.note_location(key, vtier, vreal)
            with self._lock:
                self._open_counts[key] += 1
                self._access_clock[key] = time.monotonic()
            return _SeaFile(self, key, raw, vtier, False, vreal)
        if self.federation is not None:
            pulled = self._pull_from_peer(key)
            if pulled is not None:
                vtier, vreal = pulled
                try:
                    raw = io.open(vreal, mode, **kw)
                except OSError:
                    raw = None
                if raw is not None:
                    self.telemetry.record_degraded_read()
                    with self._lock:
                        self._open_counts[key] += 1
                        self._access_clock[key] = time.monotonic()
                    return _SeaFile(self, key, raw, vtier, False, vreal)
        raise exc

    def _relocate_write(self, sf: _SeaFile, data, exc: OSError, pre_pos: int) -> int:
        """A cache-root write hit ENOSPC/EDQUOT mid-stream: trip the
        root's breaker (capacity exhaustion opens it instantly — retrying
        cannot make room) and migrate the half-written handle to wherever
        placement now lands (another root, a slower tier, or base),
        carrying the already-flushed prefix over. ``pre_pos`` is the
        handle's logical position captured *before* the failed write —
        the failure may have pushed a prefix of ``data`` through to the
        raw fd (post-failure ``tell()`` counts those bytes), so the
        migrated handle is rewound to ``pre_pos`` and ``data`` rewritten
        from there, overwriting any partially-landed prefix the copy
        carried over instead of duplicating it. Returns the write's
        byte count on success; re-raises the original error when the
        buffered prefix cannot be flushed (the device is genuinely full
        and holds bytes we cannot recover), the handle is text-mode, or
        placement offers nowhere new to go."""
        key = sf._key
        raw = sf._raw
        if isinstance(raw, io.TextIOBase):
            raise exc  # opaque text-mode positions: no safe migration
        with self.key_lock(key):
            old_tier, old_real, old_res = sf._tier, sf._real, sf._reservation
            root = old_tier.root_of(old_real)
            if root is not None:
                self.health.trip(root, "enospc")
            try:
                # bytes written *before* this call must reach the disk so
                # the prefix copy below captures them; a failing flush
                # means the buffer still holds bytes we cannot recover
                raw.flush()
            except (OSError, ValueError):
                raise exc from None
            make_room = self._lru_make_room if self.config.lru_evict else None
            new_tier, new_root, new_res = self.policy.place_new(
                reserve=True, make_room=make_room
            )
            new_real = os.path.join(new_root, key)
            if os.path.abspath(new_real) == os.path.abspath(old_real):
                # single-root hierarchy with no base room: nowhere to go
                self.policy.release_write(new_tier, new_res)
                raise exc
            try:
                os.makedirs(os.path.dirname(new_real), exist_ok=True)
                # written in place like any application write handle: the
                # registered writer + key lock already divert readers for
                # the whole open, exactly as the normal write path does
                with open(old_real, "rb") as fi, open(  # seacheck: ignore[atomic-commit]
                    new_real, "wb"
                ) as fo:  # seacheck: ignore[atomic-commit]
                    _shutil.copyfileobj(fi, fo)
                new_raw = io.open(new_real, "r+b")  # seacheck: ignore[atomic-commit]
                new_raw.seek(pre_pos)
            except OSError:
                self.policy.release_write(new_tier, new_res)
                try:
                    os.unlink(new_real)
                except OSError:
                    pass
                raise exc from None
            # settle the abandoned placement: reservation back, partial
            # file gone, stale ledger entry (overwrite-in-place) dropped
            if sf._fd is not None:
                self._fd_index.pop(sf._fd, None)
            try:
                raw.close()
            except OSError:
                pass
            self.policy.release_write(old_tier, old_res)
            try:
                os.unlink(old_real)
            except OSError:
                pass
            if root is not None:
                old_tier.note_removed(root, key)
            self._fed_unpublish(key)  # close re-publishes the new replica
            self.resolver.invalidate(key)
            self.resolver.note_location(key, new_tier, new_real, verified=False)
            sf._raw = new_raw
            sf._tier = new_tier
            sf._real = new_real
            sf._reservation = new_res
            try:
                sf._fd = new_raw.fileno()
            except (OSError, ValueError, AttributeError):
                sf._fd = None
            if sf._fd is not None:
                self._fd_index[sf._fd] = (key, new_tier, new_real)
            return new_raw.write(data)

    # -- federation (peer-aware miss resolution) -----------------------------
    def _fed_publish(self, key: str, root: str, nbytes: int) -> None:
        """Advertise a cache replica to the cluster registry (no-op when
        federation is off; best-effort — registry failures never fail the
        data path)."""
        if self.federation is not None:
            self.federation.publish(key, root, nbytes)

    def _fed_unpublish(self, key: str) -> None:
        if self.federation is not None:
            self.federation.unpublish(key)

    def _fed_republish(self, key: str, tier: Tier, real: str) -> None:
        """Re-advertise ``key`` after a mutation landed at ``real``: cache
        destinations publish the new replica (new size), persistent ones
        just drop this node's stale entry."""
        if self.federation is None:
            return
        root = tier.root_of(real) if not tier.persistent else None
        if root is None:
            self.federation.unpublish(key)
            return
        try:
            nbytes = os.path.getsize(real)
        except OSError:
            self.federation.unpublish(key)
            return
        self.federation.publish(key, root, nbytes)

    def _pull_from_peer(self, key: str) -> tuple[Tier, str] | None:
        """Pull a live peer's cache replica of ``key`` into a local cache
        tier (the peer-hit resolution tier). Called under the key lock.
        Returns ``(tier, real)`` of the new local replica, or None — the
        caller then falls through to whatever it already had (base
        replica, or a genuine miss).

        Degradation is always toward the base tier: a candidate whose
        pull fails (peer died or evicted mid-pull — the engine's atomic
        commit guarantees no partial file and no leaked reservation) is
        expunged from the registry and the next candidate tried; a full
        local cache skips the pull entirely rather than evicting for it."""
        fed = self.federation
        if fed is None:
            return None
        for node, src, size in self.resolver.resolve_peer(key):
            choice = self.policy.select_cache_for_prefetch(size)
            if choice is None:
                return None  # no cache room: serve from base
            ctier, croot = choice
            dst = os.path.join(croot, key)
            try:
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                result = self.transfer.peer_pull(
                    src, dst, dst_tier=ctier, dst_root=croot, key=key
                )
            except OSError:
                self.telemetry.record_peer_fallback()
                fed.expunge(key, node)
                continue
            self.resolver.note_location(key, ctier, dst)
            fed.publish(key, croot, result.nbytes)
            self.telemetry.record_peer_hit(result.nbytes)
            return ctier, dst
        return None

    def _on_close(
        self,
        key: str,
        tier: Tier,
        writing: bool,
        nbytes: int,
        dt: float,
        real: str | None = None,
        reservation=None,
        fast: bool = False,
    ):
        if writing:
            with self.telemetry.span("sea.close"):
                self._commit_write(key, tier, nbytes, dt, real, reservation)
                self._release_open(key, writing)
            return
        if fast:
            # fast-path reads batch their I/O counters per thread — no
            # telemetry mutex on the hot close either
            self.telemetry.local().record_read(tier.name, max(nbytes, 0))
        else:
            self.telemetry.record_io(tier.name, read=max(nbytes, 0))
        self._release_open(key, writing)

    def _commit_write(self, key: str, tier: Tier, nbytes: int, dt: float,
                      real: str | None, reservation) -> None:
        if real is not None:
            # commit the actual on-disk size against the reservation
            # BEFORE dropping the open-count: once the count hits zero
            # the flusher may evict the file, and a late commit would
            # resurrect a ghost ledger entry.
            root = tier.root_of(real)
            try:
                actual = os.path.getsize(real)
            except OSError:
                actual = max(nbytes, 0)
            if root is not None:
                self.policy.commit_write(tier, reservation, root, key, actual)
            else:
                self.policy.release_write(tier, reservation)
            self.resolver.note_location(key, tier, real)
            if root is not None and not tier.persistent:
                self._fed_publish(key, root, actual)
                # a committed application write is health evidence —
                # this is what lets a half-open probe write re-admit
                # a recovered root
                self.health.record_success(root, dt)
        self.telemetry.record_io(tier.name, written=max(nbytes, 0))

    def _release_open(self, key: str, writing: bool) -> None:
        """Drop one open of ``key``; the last close notifies the close
        listeners (the flusher)."""
        with self._lock:
            if writing:
                self._drop_writer(key)  # self._lock is reentrant
            self._open_counts[key] -= 1
            if self._open_counts[key] <= 0:
                del self._open_counts[key]
            remaining = self._open_counts.get(key, 0)
        if remaining == 0:
            for fn in self._close_listeners:
                fn(key, writing)

    # convenience wrappers used by the framework ------------------------------
    def write_bytes(self, path: str, data: bytes) -> str:
        if (
            self.config.stripe_chunk_bytes > 0
            and len(data) > self.config.stripe_chunk_bytes
            and self.is_sea_path(path)
        ):
            if self._write_striped(path, data):
                return path
        with self.open(path, "wb") as f:
            f.write(data)
        return path

    def read_bytes(self, path: str) -> bytes:
        if self.is_sea_path(path) and self.exists(path + _STRIPE_MANIFEST_SUFFIX):
            return self._read_striped(path)
        with self.open(path, "rb") as f:
            return f.read()

    # -- striping (paper §6: 'splitting of individual files, as seen with
    # the other burst buffer file systems' — implemented as a beyond-paper
    # extension, opt-in via SeaConfig.stripe_chunk_bytes) ---------------------
    def _write_striped(self, path: str, data: bytes) -> bool:
        """Split across the same-level roots of the fastest eligible tier
        (round-robin); parts parallelize device bandwidth the way BurstFS/
        GekkoFS stripe. Returns False when no multi-root tier is eligible
        (caller falls back to whole-file placement)."""
        import json as _json

        chunk = self.config.stripe_chunk_bytes
        key = self.key_of(path)
        n_parts = -(-len(data) // chunk)
        target = None
        for tier in self.hierarchy.cache_tiers:
            roots = self.policy.eligible_roots(tier)
            if len(roots) >= 2:
                # every stripe root is about to take writes: claim each
                # breaker probe now (a root that loses the half-open race
                # drops out of this stripe set)
                roots = [r for r in roots if self.policy.claim_root(tier, r)]
                if len(roots) >= 2:
                    target = (tier, roots)
                    break
        if target is None:
            return False
        tier, roots = target
        with self.key_lock(key):
            for i in range(n_parts):
                root = roots[i % len(roots)]
                pkey = f"{key}.sea_stripe.{i:04d}"
                real = os.path.join(root, pkey)
                os.makedirs(os.path.dirname(real), exist_ok=True)
                part = data[i * chunk : (i + 1) * chunk]
                # stage + rename: a crash mid-write leaves only a .sea_tmp
                # orphan (reaped later), never a short part under the
                # resolvable stripe name
                tmp = f"{real}.{os.getpid()}{_TMP_SUFFIX}"
                with open(tmp, "wb") as f:
                    f.write(part)
                os.replace(tmp, real)
                tier.note_written(root, pkey, len(part))
                self.resolver.note_location(pkey, tier, real)
            manifest = {"n_parts": n_parts, "chunk": chunk, "total": len(data),
                        "tier": tier.name}
            with self.open(path + _STRIPE_MANIFEST_SUFFIX, "w") as f:
                f.write(_json.dumps(manifest))
        self.telemetry.record_io(tier.name, written=len(data))
        return True

    def _read_striped(self, path: str) -> bytes:
        import json as _json

        key = self.key_of(path)
        with self.open(path + _STRIPE_MANIFEST_SUFFIX) as f:
            manifest = _json.loads(f.read())
        parts = []
        with self.key_lock(key):
            for i in range(manifest["n_parts"]):
                pkey = f"{key}.sea_stripe.{i:04d}"
                located = self.resolver.resolve(pkey)
                if located is None:
                    raise FileNotFoundError(f"missing stripe part {i} of {path}")
                with open(located[1], "rb") as f:
                    parts.append(f.read())
        data = b"".join(parts)
        if len(data) != manifest["total"]:
            raise IOError(f"striped read size mismatch for {path}")
        return data

    # -- metadata ops (the other glibc wrappers) -------------------------------
    def exists(self, path: str) -> bool:
        """Existence across the hierarchy. Served from the location index
        (positive AND negative entries): answers about files mutated by
        *other* processes may lag by up to the verify window / negative
        TTL; in-process mutations are always reflected immediately."""
        if not self.is_sea_path(path):
            return os.path.exists(path)
        key = self.key_of(path)
        return (
            self.resolver.resolve(key, trust_window=True) is not None
            or self.resolver.locate_dir(key) is not None
        )

    def _any_dir(self, key: str) -> str:
        found = self.resolver.locate_dir(key)
        if found is not None:
            return found
        return os.path.join(self.hierarchy.base.roots[0], key)

    def isfile(self, path: str) -> bool:
        """True iff the path resolves to a *regular file* on some tier.
        (``locate`` uses ``lexists``, which is also true for directories —
        checking the located real path keeps POSIX ``isfile`` semantics.)"""
        if not self.is_sea_path(path):
            return os.path.isfile(path)
        key = self.key_of(path)
        found = self.resolver.resolve(key, trust_window=True)
        if found is None:
            return False
        try:
            st = os.stat(found[1])
        except FileNotFoundError:
            # the stat doubled as the verify and failed: heal and retry
            found = self.resolver.refresh(key)
            if found is None:
                return False
            try:
                st = os.stat(found[1])
            except OSError:
                return False
        except OSError:
            return False
        return stat_mod.S_ISREG(st.st_mode)

    def isdir(self, path: str) -> bool:
        """True iff some tier holds a directory at this key (a virtual
        directory exists wherever any of its children were placed)."""
        if not self.is_sea_path(path):
            return os.path.isdir(path)
        return self.resolver.locate_dir(self.key_of(path)) is not None

    def stat(self, path: str):
        """``os.stat`` over the hierarchy. A partially-staged key reports
        its full LOGICAL size either way: resolution only ever sees whole
        replicas (part files carry :data:`PART_SUFFIX`), and the sparse
        part file's ``st_size`` equals the logical size by construction —
        staging state is a placement detail, never visible in metadata."""
        if not self.is_sea_path(path):
            return os.stat(path)
        key = self.key_of(path)
        found = self.resolver.resolve(key, trust_window=True)
        if found is None:
            # the negative cache must not turn a just-created file into a
            # spurious ENOENT: one authoritative scan before falling back
            found = self.resolver.resolve(key, ignore_negative=True)
        if found is not None:
            try:
                return os.stat(found[1])
            except FileNotFoundError:
                # the stat doubled as the verify and failed: heal, retry
                found = self.resolver.refresh(key)
                if found is not None:
                    try:
                        return os.stat(found[1])
                    except FileNotFoundError:
                        pass  # removed again mid-retry: fall through
        try:
            return os.stat(self._any_dir(key))
        except FileNotFoundError:
            # report the user's mount path, not the translated tier path
            raise FileNotFoundError(
                errno.ENOENT, os.strerror(errno.ENOENT), path
            ) from None

    def getsize(self, path: str) -> int:
        return self.stat(path).st_size

    def listdir(self, path: str) -> list[str]:
        """Union of entries across tiers (a directory is virtual: its
        children may be spread over several devices). Served from the
        resolver's per-directory child index when its per-root signatures
        still verify."""
        if not self.is_sea_path(path):
            return os.listdir(path)
        seen = self.resolver.listdir(self.key_of(path))
        if seen is None:
            raise FileNotFoundError(errno.ENOENT, os.strerror(errno.ENOENT), path)
        # the shared ledger / flusher-coordination store is bookkeeping
        # living inside each root, not application data — and an in-flight
        # flush's .sea_tmp staging file must never leak into the union
        seen.discard(LEDGER_DIRNAME)
        return sorted(
            n
            for n in seen
            if not n.endswith(_TMP_SUFFIX) and not n.endswith(PART_SUFFIX)
        )

    def makedirs(
        self, path: str, mode: int = 0o777, exist_ok: bool = False
    ) -> None:
        """Directories are created lazily per tier on write; creating them
        on the base tier gives tools a POSIX-visible directory. Mirrors
        ``os.makedirs`` — including the positional ``mode`` argument,
        which the intercept layer forwards verbatim."""
        if not self.is_sea_path(path):
            os.makedirs(path, mode, exist_ok=exist_ok)
            return
        key = self.key_of(path)
        os.makedirs(
            os.path.join(self.hierarchy.base.roots[0], key),
            mode,
            exist_ok=exist_ok,
        )

    def _drop_replicas(
        self, key: str, *, keep: str | None = None, replicas=None
    ) -> int:
        """Remove every on-disk replica of ``key`` across every root of
        every tier (``locate_all`` — a tier may hold copies on several
        roots), except ``keep``. ``replicas`` lets a caller that already
        ran the locate cascade pass its result in. The caller holds the
        key lock and owns the resolver invalidation. Returns the number
        dropped."""
        keep_ap = os.path.abspath(keep) if keep is not None else None
        dropped = 0
        if replicas is None:
            replicas = self.hierarchy.locate_all(key)
        for tier, real in replicas:
            if keep_ap is not None and os.path.abspath(real) == keep_ap:
                continue
            try:
                # callers own the resolver invalidation + fed unpublish
                # (contract in the docstring above)
                os.remove(real)  # seacheck: ignore[invalidation-completeness]
            except FileNotFoundError:
                continue  # raced an evict: already gone
            root = tier.root_of(real)
            if root is not None:
                tier.note_removed(root, key)
            dropped += 1
        return dropped

    def remove(self, path: str) -> None:
        if not self.is_sea_path(path):
            os.remove(path)
            return
        key = self.key_of(path)
        with self.key_lock(key):
            # one full-scan pass enumerates EVERY replica (COPY mode keeps
            # a base copy; a tier may even hold copies on several roots —
            # the seed's per-tier ``locate`` probe stopped at the first),
            # then all of them go atomically under the key lock with a
            # single resolver invalidation.
            replicas = self.hierarchy.locate_all(key)
            if not replicas:
                self.resolver.invalidate(key)
                raise FileNotFoundError(
                    errno.ENOENT, os.strerror(errno.ENOENT), path
                )
            self._drop_replicas(key, replicas=replicas)
            self._discard_extents(key)
            self.resolver.invalidate(key)
            self._fed_unpublish(key)

    def rmdir(self, path: str) -> None:
        """Remove an (empty) directory under the mount. A directory is a
        virtual union, so the removal visits every root of every tier;
        roots where it is empty are pruned even if another root still
        holds entries, in which case ENOTEMPTY is raised afterwards (the
        union still lists the survivors). FileNotFoundError if the
        directory existed on no root."""
        if not self.is_sea_path(path):
            os.rmdir(path)
            return
        key = self.key_of(path)
        found = False
        not_empty = False
        for tier in self.hierarchy.tiers:
            for root in tier.roots:
                real = os.path.join(root, key)
                if not os.path.isdir(real):
                    continue
                found = True
                try:
                    os.rmdir(real)
                except OSError as e:
                    if e.errno == errno.ENOTEMPTY:
                        not_empty = True
                    else:
                        raise
        if not found:
            raise FileNotFoundError(
                errno.ENOENT, os.strerror(errno.ENOENT), path
            )
        if not_empty:
            raise OSError(errno.ENOTEMPTY, os.strerror(errno.ENOTEMPTY), path)

    def rename(self, src: str, dst: str) -> None:
        s_in, d_in = self.is_sea_path(src), self.is_sea_path(dst)
        if not s_in and not d_in:
            os.replace(src, dst)
            return
        if s_in and d_in:
            skey, dkey = self.key_of(src), self.key_of(dst)
            # sorted-by-key acquisition, matching copyfile: two-key
            # operations must share one global lock order or a rename
            # and a copy of the same pair can ABBA-deadlock
            locks = [self.key_lock(k) for k in sorted({skey, dkey})]
            for lk in locks:
                lk.acquire()
            try:
                found = self.resolver.resolve(skey, check_faster=True)
                if found is None:
                    raise FileNotFoundError(src)
                tier, real = found
                # same-tier rename keeps the file on its device (cheap)
                droot = real[: -len(skey)] if real.endswith(skey) else None
                if droot is None:
                    droot = tier.roots[0]
                dreal = os.path.join(droot, dkey)
                os.makedirs(os.path.dirname(dreal), exist_ok=True)
                # drop stale copies of dst on other tiers/roots first
                self._drop_replicas(dkey, keep=dreal)
                self._discard_extents(skey)
                self._discard_extents(dkey)
                os.replace(real, dreal)
                self.resolver.invalidate(skey)
                self._fed_unpublish(skey)
                sroot = tier.root_of(real)
                if sroot is not None:
                    tier.note_removed(sroot, skey)
                owner = self.hierarchy.owner_of(dreal)
                self._fed_unpublish(dkey)
                if owner is not None:
                    self.resolver.note_location(dkey, owner[0], dreal)
                    try:
                        nbytes = os.path.getsize(dreal)
                        owner[0].note_written(owner[1], dkey, nbytes)
                        if not owner[0].persistent:
                            self._fed_publish(dkey, owner[1], nbytes)
                    except OSError:
                        pass
                else:
                    self.resolver.invalidate(dkey)
            finally:
                for lk in reversed(locks):
                    lk.release()
            return
        # crossing the mount boundary (exactly one side is inside): copy
        # semantics, routed through the transfer engine — the destination
        # appears atomically via .sea_tmp + os.replace, with ledger
        # admission held against the destination root before bytes move,
        # so a concurrent reader (or a crash) never observes a partial
        # file and capped roots cannot be over-committed.
        if d_in:
            dkey = self.key_of(dst)
            with self.key_lock(dkey):
                # _resolve_write creates the destination's parent dir and
                # holds the admission reservation (released by the engine
                # on any failure, committed with the actual size)
                dtier, rdst, res = self._resolve_write(dkey, reserve=True)
                self.transfer.copy(
                    src,
                    rdst,
                    src_tier=None,
                    dst_tier=dtier,
                    dst_root=dtier.root_of(rdst),
                    key=dkey,
                    reservation=res,
                )
                # drop stale replicas of dst on other tiers/roots (mirrors
                # the in-mount rename): the overwrite landed on the
                # fastest copy, and an old slower replica must not
                # resurface after an eviction
                self._drop_replicas(dkey, keep=rdst)
                self._discard_extents(dkey)
                self.resolver.invalidate(dkey)
                self.resolver.note_location(dkey, dtier, rdst)
                self._fed_republish(dkey, dtier, rdst)
            os.remove(src)
        else:
            skey = self.key_of(src)
            with self.key_lock(skey):
                # hold the key lock across resolve + copy: the flusher
                # must not move/evict the source mid-transfer
                found = self.resolver.resolve(skey, ignore_negative=True)
                if found is None:
                    raise FileNotFoundError(
                        errno.ENOENT, os.strerror(errno.ENOENT), src
                    )
                stier, rsrc = found
                os.makedirs(
                    os.path.dirname(os.path.abspath(dst)), exist_ok=True
                )
                self.transfer.copy(rsrc, dst, src_tier=stier, dst_tier=None)
            self.remove(src)

    def copyfile(self, src: str, dst: str, *, follow_symlinks: bool = True) -> str:
        """``shutil.copyfile`` semantics over the hierarchy, with the
        bytes moved through the transfer engine: chunked zero-copy
        streaming, atomic ``.sea_tmp`` + ``os.replace`` commit, and
        ledger admission held against the destination root before bytes
        move (the seed's intercepted ``copyfileobj`` loop had none of
        these, and readers could observe a partial destination).

        ``follow_symlinks`` is handled explicitly instead of being
        silently dereferenced: a symlink source is re-created with
        ``os.symlink`` when the destination is outside the mount, and
        **rejected** when it is inside (the hierarchy stores regular
        files — a symlink cannot be placed, flushed, or staged)."""
        s_in, d_in = self.is_sea_path(src), self.is_sea_path(dst)
        if not s_in and not d_in:
            return _shutil.copyfile(src, dst, follow_symlinks=follow_symlinks)
        skey = self.key_of(src) if s_in else None
        if s_in and d_in and skey == self.key_of(dst):
            # shutil parity: copying a file onto itself raises and is a
            # no-op — checked by KEY (two spellings of one mount path
            # must not reach the replica-dropping overwrite below)
            raise _shutil.SameFileError(f"{src!r} and {dst!r} are the same file")
        if not follow_symlinks:
            sprobe = src
            if s_in:
                located = self.resolver.resolve(skey, ignore_negative=True)
                sprobe = located[1] if located is not None else None
            if sprobe is not None and os.path.islink(sprobe):
                if d_in:
                    raise NotImplementedError(
                        "copyfile(follow_symlinks=False): symlink copies "
                        "into a Sea mount are not supported"
                    )
                os.symlink(os.readlink(sprobe), dst)
                return dst
        if d_in:
            dkey = self.key_of(dst)
            # deterministic (sorted-by-key) acquisition order: concurrent
            # opposite-direction copies of the same pair must not ABBA
            keys = sorted({skey, dkey} if s_in else {dkey})
            locks = [self.key_lock(k) for k in keys]
            for lk in locks:
                lk.acquire()
            try:
                if s_in:
                    located = self.resolver.resolve(skey, ignore_negative=True)
                    if located is None:
                        raise FileNotFoundError(
                            errno.ENOENT, os.strerror(errno.ENOENT), src
                        )
                    stier, rsrc = located
                else:
                    stier, rsrc = None, src
                dtier, rdst, res = self._resolve_write(dkey, reserve=True)
                if os.path.abspath(rdst) == os.path.abspath(rsrc):
                    self.policy.release_write(dtier, res)
                    raise _shutil.SameFileError(
                        f"{src!r} and {dst!r} are the same file"
                    )
                # preserve_stat=False: shutil.copyfile copies DATA only —
                # destination permissions come from the umask and the
                # mtime is fresh (copy2 is the stat-preserving variant)
                self.transfer.copy(
                    rsrc,
                    rdst,
                    src_tier=stier,
                    dst_tier=dtier,
                    dst_root=dtier.root_of(rdst),
                    key=dkey,
                    reservation=res,
                    preserve_stat=False,
                )
                # the overwrite landed on the fastest copy: stale slower
                # replicas must not resurface after an eviction
                self._drop_replicas(dkey, keep=rdst)
                self._discard_extents(dkey)
                self.resolver.invalidate(dkey)
                self.resolver.note_location(dkey, dtier, rdst)
                self._fed_republish(dkey, dtier, rdst)
            finally:
                for lk in reversed(locks):
                    lk.release()
            # the destination is a committed write: the flusher must
            # learn about it exactly as it learns about a closed write
            # handle (the replaced intercept path flushed via that close
            # event; without this, a flushlist destination would sit
            # cache-only until drain)
            if self.open_count(dkey) == 0:
                for fn in self._close_listeners:
                    fn(dkey, True)
            return dst
        # src inside the mount, dst external
        with self.key_lock(skey):
            located = self.resolver.resolve(skey, ignore_negative=True)
            if located is None:
                raise FileNotFoundError(
                    errno.ENOENT, os.strerror(errno.ENOENT), src
                )
            stier, rsrc = located
            if os.path.exists(dst) and os.path.samefile(rsrc, dst):
                raise _shutil.SameFileError(
                    f"{src!r} and {dst!r} are the same file"
                )
            self.transfer.copy(
                rsrc, dst, src_tier=stier, dst_tier=None, preserve_stat=False
            )
        return dst

    # -- LRU room-making (beyond-paper, opt-in) --------------------------------
    def _lru_make_room(self) -> bool:
        """Evict least-recently-used closed files from cache tiers until a
        cache root becomes eligible again. Only files whose mode is KEEP or
        REMOVE (i.e. not awaiting flush) are candidates."""
        candidates: list = []  # (hot, atime, key, real, tier, root)
        for tier in self.hierarchy.cache_tiers:
            for root in tier.roots:
                for dirpath, dirnames, files in os.walk(root):
                    if LEDGER_DIRNAME in dirnames:
                        dirnames.remove(LEDGER_DIRNAME)
                    for fn in files:
                        real = os.path.join(dirpath, fn)
                        if fn.endswith(_TMP_SUFFIX):
                            # never evict an in-flight staging file out
                            # from under a racing os.replace; dead ones
                            # are reclaimed on the spot
                            self.transfer.maybe_reap_orphan(real)
                            continue
                        if fn.endswith(PART_SUFFIX):
                            # partial extent replicas are evicted block-
                            # wise (punch pass below), never whole-file
                            continue
                        key = os.path.relpath(real, root)
                        if self.open_count(key):
                            continue
                        mode = self.rules.mode(key)
                        if mode in (Mode.KEEP, Mode.REMOVE):
                            at = self._access_clock.get(key, 0.0)
                            # predicted-hot keys (speculatively staged,
                            # application expected imminently) are
                            # evicted LAST — room-making must not throw
                            # readahead work away moments before it pays
                            hot = self.prefetcher.is_hot(key)
                            candidates.append((hot, at, key, real, tier, root))
        candidates.sort(key=lambda c: (c[0], c[1], c[2], c[3]))
        freed_any = False
        for _hot, _at, key, real, vtier, vroot in candidates:
            with self.key_lock(key):
                if self.open_count(key):
                    continue
                try:
                    nbytes = os.path.getsize(real)
                    os.remove(real)
                    vtier.note_removed(vroot, key)
                    self.resolver.invalidate(key)
                    self._fed_unpublish(key)
                    self.telemetry.record_evict(nbytes)
                    freed_any = True
                except OSError:
                    continue
            for tier in self.hierarchy.cache_tiers:
                if self.policy.eligible_roots(tier):
                    return True
        if self.extents is not None:
            # whole files alone didn't make a root eligible: punch cold
            # staged extents too (block-granular room-making)
            for tier in self.hierarchy.cache_tiers:
                for root in tier.roots:
                    if self._extent_make_room(root, self.policy.required_bytes):
                        freed_any = True
                    if self.policy.eligible_roots(tier):
                        return True
        return freed_any

    def stage_to_cache(self, key: str, *, cancel=None) -> int:
        """Stage one base-tier file into the fastest cache root with room
        (the prefetch/staging primitive shared by ``Flusher.prefetch``,
        the readahead predictor, and the data pipeline): under the key
        lock — a racing evict/flusher move can't pull the source out
        from under the copy — with ledger admission reserved before
        bytes move and the staging tmp cleaned up on failure. ``cancel``
        (speculative staging) aborts cooperatively before admission and
        between chunks. Best-effort: returns the bytes staged, or 0 when
        the key is gone, already cached, out of room, cancelled, or the
        transfer failed (callers fall back to the base copy)."""
        with self.key_lock(key):
            if cancel is not None and cancel.is_set():
                return 0  # stale prediction: don't even resolve
            if self.extents is not None and self.extents.get(key) is not None:
                # the key streams through a partial replica: staging is
                # per-extent (stage_extent), not whole-file
                return 0
            located = self.resolver.resolve(key, ignore_negative=True)
            if located is None or not located[0].persistent:
                return 0  # gone, or already cached
            try:
                nbytes = os.path.getsize(located[1])
            except OSError:
                return 0  # removed since resolution
            slot = self.policy.select_cache_for_prefetch(nbytes)
            if slot is None:
                return 0
            ctier, croot = slot
            dst = os.path.join(croot, key)
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            try:
                result = self.transfer.copy(
                    located[1],
                    dst,
                    src_tier=located[0],
                    dst_tier=ctier,
                    dst_root=croot,
                    key=key,
                    admit="require",
                    cancel=cancel,
                )
            except OSError:
                # admission lost to a racing writer, a cancellation, or
                # an I/O error (engine errors preserve their POSIX
                # class): staging is best-effort — the file simply stays
                # on the base tier
                return 0
            # staging created a faster replica: point the index straight
            # at it
            self.resolver.note_location(key, ctier, dst)
            self._fed_publish(key, croot, result.nbytes)
            self.telemetry.record_prefetch(result.nbytes)
            return result.nbytes

    # -- extent plane (block-granular staging; opt-in via extent_map) ----------
    def _discard_extents(self, key: str) -> None:
        """Drop a key's partial replica (overwrite/remove/rename/truncate
        make per-extent state stale) and settle its ledger entry."""
        if self.extents is None:
            return
        em = self.extents.discard(key)
        if em is not None:
            em.tier.note_removed(em.root, em.part_rel)

    def _open_extent_read(self, key: str, tier: Tier, real: str):
        """Route one binary read open through the extent plane (caller
        holds the key lock; ``real`` resolved on the persistent base).
        Returns None to fall back to the whole-file path: size
        unreadable, file fits in one extent, or no cache root has room
        for even one extent."""
        try:
            size = os.path.getsize(real)
        except OSError:
            return None
        if size <= self.extents.extent_bytes:
            return None  # single extent: whole-file staging is equivalent
        em = self.extents.load(key, self.hierarchy.cache_tiers)
        if em is not None and (em.size != size or em.dead):
            self._discard_extents(key)  # base rewritten: journal is stale
            em = None
        if em is None:
            slot = self._select_extent_root(self.extents.extent_bytes)
            if slot is None:
                return None  # no room for one extent: stream from base
            ctier, croot = slot
            em = self.extents.create(key, ctier, croot, size)
        em.tier.note_written(
            em.root, em.part_rel, ExtentStore.disk_usage(em)
        )
        try:
            raw = _ExtentRaw(self, key, em, real, tier)
        except OSError:
            self._discard_extents(key)
            return None
        with self._lock:
            self._open_counts[key] += 1
            self._access_clock[key] = time.monotonic()
        return _SeaFile(
            self, key, io.BufferedReader(raw), em.tier, False, em.part_real
        )

    def _fault_extent(self, em, idx: int) -> bool:
        """Synchronous read-fault of one extent — the reader blocks for
        O(1 extent), never O(file). Best-effort: False streams the read
        from the base replica instead."""
        if em.dead:
            return False
        with self.key_lock(em.key):
            if em.dead:
                return False
            if em.is_valid(idx):
                return True
            return self._stage_extent_locked(em, idx) > 0

    def stage_extent(self, key: str, idx: int, *, cancel=None) -> int:
        """Stage one extent of ``key``'s partial replica — the per-extent
        analogue of :meth:`stage_to_cache`, driven by the within-file
        readahead predictor. Returns the bytes staged (0 = gone, already
        staged, out of room, cancelled, or failed)."""
        if self.extents is None:
            return 0
        with self.key_lock(key):
            if cancel is not None and cancel.is_set():
                return 0
            em = self.extents.get(key)
            if (
                em is None
                or em.dead
                or idx >= em.n_extents
                or em.is_valid(idx)
            ):
                return 0
            return self._stage_extent_locked(em, idx, cancel=cancel)

    def _stage_extent_locked(self, em, idx: int, *, cancel=None) -> int:
        """The staging step (caller holds the key lock): admission at
        EXTENT granularity — ``required`` is one extent, not the paper's
        whole-file headroom, which is what admits files bigger than the
        tier — then a ranged copy committed by the validity journal."""
        start, length = em.extent_range(idx)
        located = self.resolver.resolve(em.key, ignore_negative=True)
        if located is None or not located[0].persistent:
            return 0  # base replica gone, or a full cache replica exists
        admitted, res = self._admit_extent(em.tier, em.root, length)
        if not admitted and self.config.lru_evict:
            if self._extent_make_room(em.root, length):
                admitted, res = self._admit_extent(em.tier, em.root, length)
        if not admitted:
            return 0
        try:
            faults.fire("extents.stage", path=em.part_real, cancel=cancel)
            self.transfer.copy_range(
                located[1],
                em.part_real,
                start,
                length,
                src_tier=located[0],
                dst_tier=em.tier,
                dst_root=em.root,
                cancel=cancel,
            )
        except OSError:
            # cancelled, or an I/O error (engine errors keep their POSIX
            # class): per-extent staging is best-effort — the reader
            # falls back to the base replica. The failed attempt may have
            # committed chunks into the sparse file: punch them back out
            # (best-effort) and re-note the REAL disk usage, or the walk
            # and the ledger would disagree by the torn chunks.
            em.tier.release_write(res)
            try:
                fd = os.open(em.part_real, os.O_RDWR)
                try:
                    # punches an extent that was never marked valid — the
                    # resolver and peers never saw it, nothing to invalidate
                    punch_hole(fd, start, length)  # seacheck: ignore[invalidation-completeness]
                finally:
                    os.close(fd)
            except OSError:
                pass
            em.tier.note_written(
                em.root, em.part_rel, ExtentStore.disk_usage(em)
            )
            return 0
        self.extents.mark_valid(em, idx)
        em.tier.commit_write(
            res, em.root, em.part_rel, ExtentStore.disk_usage(em)
        )
        self.telemetry.record_extent_staged(length)
        if em.complete:
            self._promote_extents(em)
        return length

    def _promote_extents(self, em) -> None:
        """Every extent landed: the partial replica becomes a plain
        whole-file replica (atomic rename) and the ledger swaps the part
        entry for the final file — a fully-staged key degenerates to
        exactly the whole-file plane's state."""
        try:
            final = self.extents.promote(em)
        except OSError:
            return
        em.tier.note_removed(em.root, em.part_rel)
        try:
            em.tier.note_written(em.root, em.key, file_disk_usage(final))
        except OSError:
            pass
        self.resolver.note_location(em.key, em.tier, final)

    def _admit_extent(self, tier: Tier, root: str, nbytes: int):
        """Atomic per-extent admission. Returns (admitted, reservation)."""
        if tier.spec.capacity is None:
            if not tier.admissible(root, required=nbytes, nbytes=nbytes):
                return False, None
            return True, tier.reserve_write(root, nbytes)
        res = tier.ledger.try_reserve(
            root, nbytes, capacity=tier.spec.capacity, required=nbytes
        )
        return res is not None, res

    def _select_extent_root(self, nbytes: int) -> tuple[Tier, str] | None:
        """Fastest cache root with room for ONE extent. (The whole-file
        planes demand the ``n_procs * max_file_size`` headroom; the
        extent plane admits block by block, so a tier smaller than the
        largest file still qualifies.)"""
        for tier in self.hierarchy.cache_tiers:
            roots = list(tier.roots)
            self.policy.rng.shuffle(roots)
            for r in roots:
                if (
                    self.policy._root_allowed(tier, r)
                    and tier.free_bytes(r) >= nbytes
                    and self.policy.claim_root(tier, r)  # chosen for I/O
                ):
                    return tier, r
        return None

    def _extent_make_room(self, root: str, need: int) -> bool:
        """Punch the least-recently-read staged extents under ``root``
        until ``need`` bytes are deallocated — extent-granular eviction:
        cold blocks of hot (even currently-open) files go first, with
        predicted-hot extents shielded the way whole files are."""
        if self.extents is None:
            return False
        cands: list = []
        for em in self.extents.maps():
            if em.dead or em.root != root:
                continue
            for idx in sorted(em.valid):
                hot = self.prefetcher.is_hot(extent_token(em.key, idx))
                cands.append((hot, em.atime.get(idx, 0.0), em.key, idx, em))
        cands.sort(key=lambda c: (c[0], c[1], c[2], c[3]))
        freed = 0
        for _hot, _at, _key, idx, em in cands:
            n = self.extents.punch(em, idx)
            if n <= 0:
                continue
            self.telemetry.record_extent_punched(n)
            em.tier.note_written(
                em.root, em.part_rel, ExtentStore.disk_usage(em)
            )
            freed += n
            if freed >= need:
                return True
        return freed >= need

    # -- truncate (ledger-settled; bypassing it drifts used-bytes) -------------
    def truncate(self, path: str, length: int) -> None:
        """``os.truncate`` over the hierarchy: applied to the fastest
        replica, stale slower replicas dropped, the ledger re-noted with
        the new size, and resolver/extent state invalidated — a truncate
        that bypasses Sea otherwise drifts used-bytes until the next
        reconcile and leaves partial extent replicas serving dead data."""
        if not self.is_sea_path(path):
            _os_truncate(path, length)
            return
        key = self.key_of(path)
        with self.key_lock(key):
            found = self.resolver.resolve(
                key, check_faster=True, ignore_negative=True
            )
            if found is None:
                raise FileNotFoundError(
                    errno.ENOENT, os.strerror(errno.ENOENT), path
                )
            tier, real = found
            _os_truncate(real, length)
            self._drop_replicas(key, keep=real)
            self._discard_extents(key)
            root = tier.root_of(real)
            if root is not None:
                try:
                    tier.note_written(root, key, file_disk_usage(real))
                except OSError:
                    pass
            self.resolver.invalidate(key)
            self.resolver.note_location(key, tier, real)
            self._fed_republish(key, tier, real)

    def ftruncate(self, fd: int, length: int) -> None:
        """``os.ftruncate`` for fds opened through SeaFS: the syscall,
        then the same ledger/extent settlement as :meth:`truncate`.
        Foreign fds get the plain syscall and no bookkeeping."""
        _os_ftruncate(fd, length)
        info = self._fd_index.get(fd)
        if info is None:
            return
        key, tier, real = info
        self._discard_extents(key)
        root = tier.root_of(real)
        if root is not None:
            try:
                tier.note_written(root, key, file_disk_usage(real))
            except OSError:
                pass
        self._fed_republish(key, tier, real)

    def persist(self, path: str) -> str:
        """Ensure a durable copy exists on the base (persistent) tier,
        keeping any cache copy (explicit COPY — used for input datasets
        that eviction must never orphan). Bytes move through the transfer
        engine: chunked, atomically committed, ledger-accounted."""
        key = self.key_of(path)
        with self.key_lock(key):
            located = self.resolver.resolve(key)
            if located is None:
                raise FileNotFoundError(
                    errno.ENOENT, os.strerror(errno.ENOENT), path
                )
            tier, real = located
            base = self.hierarchy.base
            base_root = base.roots[0]
            dst = os.path.join(base_root, key)
            if tier.persistent or os.path.abspath(real) == os.path.abspath(dst):
                return dst
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            result = self.transfer.copy(
                real,
                dst,
                src_tier=tier,
                dst_tier=base,
                dst_root=base_root,
                key=key,
                admit="reserve",
            )
            self.telemetry.record_flush(result.nbytes)
            return dst

    # -- introspection ----------------------------------------------------------
    def where(self, path: str) -> str | None:
        """Tier name currently holding the file (fastest hit), or None."""
        if not self.is_sea_path(path):
            return None
        # a COPY-flushed file keeps its fast replica: probe above the
        # cached hit so introspection reports the true fastest tier
        found = self.resolver.resolve(self.key_of(path), check_faster=True)
        return found[0].name if found else None

    def wipe(self) -> None:
        if self.extents is not None:
            self.extents.clear()  # on-disk parts/journals go with the roots
        if self.federation is not None:
            # peers must stop pulling from roots that are about to vanish
            self.federation.unpublish_all()
        for tier in self.hierarchy:
            tier.wipe()
        self.resolver.invalidate_all()
