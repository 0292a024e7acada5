"""Namespace resolver — O(1) key→location resolution with verify-on-hit.

The paper's design is stateless: "a file's location IS its state on the
file systems" — resolution probes every root of every tier with ``lexists``
until it finds the file. That cascade is correct but costs O(tiers × roots)
metadata round-trips on **every** ``open``/``stat``/``exists``/``listdir``,
and it is exactly the metadata-path latency that dominates read-heavy
scientific workloads (cf. the HSM follow-up paper in PAPERS.md).

This layer keeps the statelessness *as the source of truth* while making
the common case O(1):

- **Location index.** A sharded in-process map ``key -> (tier, real)``
  populated by every placement/commit and by every full-scan miss.
- **Verify-on-hit.** A cached hit is trusted only after one ``lstat`` of
  the cached real path. If the file moved (cross-process flusher MOVE,
  external eviction), the verify fails, the entry is dropped, and the
  resolver falls back to the full probe cascade — so no metadata server
  is needed and concurrent movers stay correct by construction.
- **Verify trust window.** A successful verify (or an in-process
  mutation) stamps the entry; for ``max_age_s`` seconds further hits
  skip even the verify ``lstat`` — the hit path is then a pure dict
  lookup, independent of tiers, roots, *and* syscall latency. Operations
  that touch the file anyway (``open``, ``stat``) use their own ENOENT
  as the failed verify and *heal* via :meth:`refresh`, so a data read
  can never be stale or spuriously missing: only pure existence
  introspection can lag an **external** mutation, bounded by the
  window. In-process mutations always invalidate/overwrite the entry
  immediately. ``max_age_s=0`` restores the strict one-lstat-per-hit
  discipline.
- **Negative caching.** A full scan that finds nothing records a negative
  entry for ``negative_ttl_s`` seconds, absorbing read-miss storms
  (repeated ``exists()`` polling) at a bounded staleness cost.
- **Faster-copy probe for writes.** Overwrites must land on the *true*
  fastest replica (the hierarchy must never diverge). A write-side
  resolve therefore additionally probes only the tiers *above* the cached
  hit — zero extra cost when the hit is already on the fastest tier.
- **Directory child index.** ``listdir`` of a virtual directory is the
  union over every root of every tier. The resolver caches that union
  keyed by the per-root directory signatures (mtime_ns + inode): a hit is
  verified with one ``stat`` per candidate root — O(roots) stats instead
  of O(roots) ``listdir`` calls + O(entries) set unions — and any external
  create/delete bumps a directory mtime, failing the verify.

Every mutation path (write placement, close/commit, ``remove``,
``rename``, LRU eviction, flusher flush/evict/move, prefetch staging,
``wipe``) notes or invalidates entries; the index never needs to be
trusted blindly, so a stale entry costs one wasted ``lstat``, never a
stale read.
"""

from __future__ import annotations

import os
import stat as stat_mod
import threading
import time

from .tiers import Hierarchy, Tier


class _Entry:
    """Positive location entry: where the key was last seen, and when the
    real path was last verified to exist (monotonic; 0 = never)."""

    __slots__ = ("tier", "real", "verified_at")

    def __init__(self, tier: Tier, real: str, verified_at: float = 0.0):
        self.tier = tier
        self.real = real
        self.verified_at = verified_at


class _Negative:
    """Negative entry: a full scan found nothing at ``ts`` (monotonic)."""

    __slots__ = ("ts",)

    def __init__(self, ts: float):
        self.ts = ts


class _DirEntry:
    """Cached virtual-directory union + the per-root signatures it is
    conditional on. ``stamps[i]`` is ``(mtime_ns, ino)`` of candidate
    directory i, or None when that root had no such directory."""

    __slots__ = ("stamps", "entries")

    def __init__(self, stamps: tuple, entries: frozenset):
        self.stamps = stamps
        self.entries = entries


class Resolver:
    """Cached key→location resolution over a :class:`Hierarchy`.

    Thread-safe; shards the index by key hash so concurrent resolutions of
    different keys do not serialize. All entries are advisory: correctness
    comes from verify-on-hit plus the full-scan fallback, never from the
    cache itself.
    """

    def __init__(
        self,
        hierarchy: Hierarchy,
        telemetry=None,
        *,
        negative_ttl_s: float = 0.05,
        verify_window_s: float = 0.05,
        n_shards: int = 16,
    ):
        self.hierarchy = hierarchy
        self.telemetry = telemetry
        self.negative_ttl_s = max(float(negative_ttl_s), 0.0)
        self.verify_window_s = max(float(verify_window_s), 0.0)
        self._shards: list[dict[str, object]] = [{} for _ in range(n_shards)]
        self._locks = [threading.Lock() for _ in range(n_shards)]
        # per-shard invalidation generation: a scan result is only stored
        # if no invalidation/note landed in its shard while the (unlocked)
        # scan ran — an index entry must never outlive the mutation that
        # invalidated it
        self._gens = [0] * n_shards
        self._dirs: dict[str, _DirEntry] = {}
        self._dir_lock = threading.Lock()

        #: bound by SeaFS when the extent plane is enabled — the extent
        #: maps are placement state (like the tiers themselves), not a
        #: cache
        self.extent_store = None

        #: bound by SeaFS when cache federation is enabled — the third
        #: resolution tier (local hit -> peer hit -> base fallback); like
        #: extent maps, the registry is cluster state
        self.federation = None

        # don't cache a directory whose mtime is this close to "now": a
        # same-mtime-tick mutation on a coarse-granularity filesystem
        # would otherwise be invisible to the signature check forever
        # (stable directories — the metadata-read-heavy case — do cache)
        self._racy_dir_ns = 2_000_000_000

    #: wholesale-clear bound per shard / for the dir cache (mirrors
    #: CompiledRules: pathological key churn must not grow memory forever)
    _SHARD_MAX = 8192
    _DIRS_MAX = 4096

    # -- telemetry plumbing -------------------------------------------------
    def _record(self, method: str, **kw) -> None:
        if self.telemetry is not None:
            getattr(self.telemetry, method)(**kw)

    # -- index shards -------------------------------------------------------
    def _shard_index(self, key: str) -> int:
        return hash(key) % len(self._shards)

    def _store(self, key: str, i: int, gen0: int, found) -> None:
        """Record a scan result, unless the shard was invalidated while
        the scan ran (the scan may have observed pre-mutation state)."""
        with self._locks[i]:
            if self._gens[i] != gen0:
                return
            shard = self._shards[i]
            if len(shard) >= self._SHARD_MAX:
                shard.clear()
            if found is not None:
                shard[key] = _Entry(found[0], found[1], time.monotonic())
            else:
                shard[key] = _Negative(time.monotonic())

    # -- file resolution ----------------------------------------------------
    def resolve(
        self,
        key: str,
        *,
        check_faster: bool = False,
        ignore_negative: bool = False,
        trust_window: bool = False,
    ) -> tuple[Tier, str] | None:
        """Locate ``key``, fastest tier first — O(1) on the hit path.

        ``check_faster=True`` (write-side resolution) additionally probes
        the tiers above a cached hit so an overwrite can never miss a
        faster replica; the probe is free when the hit is already on tier
        0. ``ignore_negative=True`` (flusher/prefetch paths) bypasses the
        negative cache so externally-created files are never skipped.
        ``trust_window=True`` (read-side hot path) skips the verify
        ``lstat`` while the entry's last verify is younger than
        ``verify_window_s`` — callers that subsequently touch the file
        must treat their own ENOENT as a failed verify and call
        :meth:`refresh` (operation-as-verify).
        """
        i = self._shard_index(key)
        shard = self._shards[i]
        lock = self._locks[i]
        with lock:
            e = shard.get(key)
        if isinstance(e, _Negative):
            if (
                not ignore_negative
                and time.monotonic() - e.ts <= self.negative_ttl_s
            ):
                self._record("record_resolve", hit=True, negative=True)
                return None
            e = None  # expired (or bypassed): fall through to the scan
        if isinstance(e, _Entry):
            now = time.monotonic()
            if (
                trust_window
                and not check_faster
                and now - e.verified_at <= self.verify_window_s
            ):
                self._record("record_resolve", hit=True)
                return e.tier, e.real
            try:
                os.lstat(e.real)
            except OSError:
                # the file moved under us (cross-process flusher MOVE,
                # external delete): drop the entry, fall back to the scan
                with lock:
                    if shard.get(key) is e:
                        del shard[key]
                self._record("record_resolve", hit=False, verify_failed=True)
            else:
                e.verified_at = now
                if check_faster and e.tier.level > 0:
                    above = self.hierarchy.locate_above(key, e.tier.level)
                    if above is not None:
                        self.note_location(key, above[0], above[1])
                        self._record("record_resolve", hit=True)
                        return above
                self._record("record_resolve", hit=True)
                return e.tier, e.real
        else:
            self._record("record_resolve", hit=False)
        with lock:
            gen0 = self._gens[i]
        found = self.hierarchy.locate(key)
        self._store(key, i, gen0, found)
        return found

    def resolve_fast(self, key: str) -> tuple[Tier, str] | None:
        """Lock-free trust-window hit, or None for *anything* else — the
        ``open`` fast path. A single GIL-atomic dict read: no shard lock,
        no telemetry, no verify ``lstat``, no fallback scan. Only a
        positive entry whose last verify is inside the trust window is
        served; the caller's own ``io.open`` doubles as the verify (its
        ENOENT sends the caller to the full slow path, which heals). With
        ``verify_window_s == 0`` (strict verify-on-hit) this never hits,
        so the fast path composes with the strict discipline."""
        if self.verify_window_s <= 0.0:
            return None
        e = self._shards[self._shard_index(key)].get(key)
        if (
            type(e) is _Entry
            and time.monotonic() - e.verified_at <= self.verify_window_s
        ):
            return e.tier, e.real
        return None

    def resolve_extent(
        self, key: str, offset: int, *, trust_window: bool = True
    ) -> tuple[Tier, str] | None:
        """Locate the tier holding byte ``offset`` of ``key`` at extent
        granularity: the cache tier's sparse part file when the covering
        extent is staged-and-valid, else None (the byte is served from
        whatever :meth:`resolve` returns — the whole-file plane).

        Same verify-on-hit discipline as :meth:`resolve`: a hit inside
        the verify trust window is a pure in-memory lookup; past it, one
        ``lstat`` of the part file re-verifies (an externally evicted
        part file drops the whole map — per-extent validity without its
        backing file is meaningless)."""
        store = self.extent_store
        if store is None:
            return None
        em = store.get(key)
        if em is None:
            return None
        if not em.is_valid(em.index_of(offset)):
            return None
        now = time.monotonic()
        if not (
            trust_window and now - em.verified_at <= self.verify_window_s
        ):
            try:
                os.lstat(em.part_real)
            except OSError:
                store.discard(key)
                self._record("record_resolve", hit=False, verify_failed=True)
                return None
            em.verified_at = now
        return em.tier, em.part_real

    def resolve_peer(self, key: str) -> list[tuple[str, str, int]]:
        """The third resolution tier (local hit -> **peer hit** -> base
        fallback): live cluster peers holding a cache replica of ``key``,
        as ``(node, real_path, size)`` candidates for a peer->cache pull.
        Empty when federation is off or the registry is unreachable —
        callers then fall through to the base tier. Peer entries are
        advisory like everything else in the resolver: a stale candidate
        costs one failed pull (the caller expunges it and falls back),
        never a wrong read."""
        fed = self.federation
        if fed is None:
            return []
        return fed.lookup(key)

    def refresh(self, key: str) -> tuple[Tier, str] | None:
        """A caller's own operation hit ENOENT on a resolved path (the
        operation doubled as the verify and failed): drop the entry,
        count the verify failure, and re-scan from scratch."""
        i = self._shard_index(key)
        with self._locks[i]:
            self._shards[i].pop(key, None)
            gen0 = self._gens[i]
        self._record("record_resolve", hit=False, verify_failed=True)
        found = self.hierarchy.locate(key)
        self._store(key, i, gen0, found)
        return found

    def note_location(
        self, key: str, tier: Tier, real: str, *, verified: bool = True
    ) -> None:
        """A mutation placed ``key`` at ``real`` on ``tier`` (write
        placement, close/commit, rename destination, prefetch staging).
        ``verified=False`` (placement before the file is materialized)
        forces the first read hit to verify. Entries are advisory: if the
        caller never materializes the file, the next resolve's verify
        simply falls back to the scan."""
        i = self._shard_index(key)
        with self._locks[i]:
            self._gens[i] += 1  # a racing scan must not clobber this note
            shard = self._shards[i]
            if len(shard) >= self._SHARD_MAX:
                shard.clear()
            shard[key] = _Entry(
                tier, real, time.monotonic() if verified else 0.0
            )
        self._drop_parent_dirs(key)

    def invalidate(self, key: str) -> None:
        """``key`` was removed/evicted/renamed away: drop whatever the
        index believes about it (one invalidation covers all replicas).
        A scan racing this mutation is fenced by the shard generation:
        its (possibly pre-mutation) result will not be stored."""
        i = self._shard_index(key)
        with self._locks[i]:
            self._gens[i] += 1
            dropped = self._shards[i].pop(key, None) is not None
        self._drop_parent_dirs(key)
        if dropped:
            self._record("record_resolver_invalidate")

    def invalidate_all(self) -> None:
        """Full reset (``wipe``)."""
        for i, (shard, lock) in enumerate(zip(self._shards, self._locks)):
            with lock:
                self._gens[i] += 1
                shard.clear()
        with self._dir_lock:
            self._dirs.clear()

    def _drop_parent_dirs(self, key: str) -> None:
        """An in-process mutation of ``key`` changes the listing of every
        ancestor directory: drop their cached unions immediately (the
        mtime signature would also catch it, but not within the same
        mtime tick on coarse-granularity filesystems)."""
        if not self._dirs:
            return
        parents = []
        d = os.path.dirname(key)
        while d:
            parents.append(d)
            d = os.path.dirname(d)
        parents.append("")
        with self._dir_lock:
            for p in parents:
                self._dirs.pop(p, None)

    # -- virtual directories ------------------------------------------------
    def _dir_candidates(self, key: str) -> list[str]:
        """Real directory paths that could contribute children of ``key``,
        fastest tier first (one per root of every tier)."""
        return [
            os.path.join(root, key) if key else root
            for tier in self.hierarchy
            for root in tier.roots
        ]

    @staticmethod
    def _dir_signature(paths: list[str]) -> tuple:
        """Per-candidate ``(mtime_ns, ino)`` (None where absent or not a
        directory). Any create/delete/rename in a directory bumps its
        mtime, so equal signatures imply an unchanged union."""
        sig = []
        for p in paths:
            try:
                st = os.stat(p)
            except OSError:
                sig.append(None)
            else:
                sig.append(
                    (st.st_mtime_ns, st.st_ino)
                    if stat_mod.S_ISDIR(st.st_mode)
                    else None
                )
        return tuple(sig)

    def listdir(self, key: str) -> set[str] | None:
        """Union of children of virtual directory ``key`` across every
        root of every tier, or None when no tier has such a directory.
        Cached; a hit costs one ``stat`` per candidate root instead of a
        ``listdir`` + set union."""
        key = "" if key == "." else key
        candidates = self._dir_candidates(key)
        with self._dir_lock:
            e = self._dirs.get(key)
        # signature FIRST, union second: a mutation racing the walk makes
        # the stored stamp stale, so the next hit re-verifies — never the
        # other way around (a post-walk stamp could mask a missed entry)
        stamps = self._dir_signature(candidates)
        if e is not None and stamps == e.stamps:
            self._record("record_dir_resolve", hit=True)
            return set(e.entries)
        self._record("record_dir_resolve", hit=False)
        seen: set[str] = set()
        found = False
        for p in candidates:
            try:
                names = os.listdir(p)
            except OSError:
                continue
            found = True
            seen.update(names)
        if not found:
            return None
        if not self._racy_stamps(stamps):
            with self._dir_lock:
                if len(self._dirs) >= self._DIRS_MAX:
                    self._dirs.clear()
                self._dirs[key] = _DirEntry(stamps, frozenset(seen))
        return seen

    def _racy_stamps(self, stamps: tuple) -> bool:
        """True when any contributing directory's mtime is within the
        racy window of "now": a mutation landing in the same mtime tick
        (coarse-granularity filesystems) would be invisible to the
        signature check, so such a union must not be cached."""
        now_ns = time.time_ns()
        return any(
            s is not None and now_ns - s[0] < self._racy_dir_ns for s in stamps
        )

    def locate_dir(self, key: str) -> str | None:
        """Real path of the fastest-tier copy of virtual directory ``key``
        (the ``_any_dir`` probe of the seed), served from the directory
        index when its signature still verifies."""
        key = "" if key == "." else key
        candidates = self._dir_candidates(key)
        with self._dir_lock:
            e = self._dirs.get(key)
        if e is not None:
            sig = self._dir_signature(candidates)
            if sig == e.stamps:
                self._record("record_dir_resolve", hit=True)
                for p, s in zip(candidates, sig):
                    if s is not None:
                        return p
                return None
        for p in candidates:
            if os.path.isdir(p):
                return p
        return None
