"""Tests for the namespace-resolver layer (cached key→location index).

Covers: hit-path behaviour (no probe cascade), verify-on-hit fallback
under cross-process moves, negative-cache expiry, invalidation under
concurrent flusher moves/evicts (zero stale reads), the per-directory
child index, and the satellite bugfixes (stat error path, remove of all
replicas).
"""

import os
import shutil
import threading
import time

import pytest

from repro.core import Sea, SeaConfig, SeaFS, TierSpec
from repro.core.flusher import Flusher
from repro.core.ledger import LEDGER_DIRNAME


def make_config(tmp_path, **kw):
    defaults = dict(
        mount=str(tmp_path / "mount"),
        tiers=[
            TierSpec(name="tmpfs", roots=(str(tmp_path / "t0"),)),
            TierSpec(name="disk", roots=(str(tmp_path / "d0"), str(tmp_path / "d1"))),
            TierSpec(name="pfs", roots=(str(tmp_path / "pfs"),), persistent=True),
        ],
        max_file_size=1 << 16,
        n_procs=2,
    )
    defaults.update(kw)
    return SeaConfig(**defaults)


class _CountingLocate:
    """Wraps Hierarchy.locate to count full probe cascades."""

    def __init__(self, hierarchy):
        self.hierarchy = hierarchy
        self.calls = 0
        self._orig = hierarchy.locate

    def __enter__(self):
        def counting(relpath):
            self.calls += 1
            return self._orig(relpath)

        self.hierarchy.locate = counting
        return self

    def __exit__(self, *exc):
        self.hierarchy.locate = self._orig


# ---------------------------------------------------------------- hit path
def test_hit_path_skips_probe_cascade(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "a/hot.bin")
    fs.write_bytes(p, b"x" * 64)
    fs.read_bytes(p)  # warm
    with _CountingLocate(fs.hierarchy) as cl:
        for _ in range(10):
            assert fs.read_bytes(p) == b"x" * 64
        assert cl.calls == 0  # every resolution served by the index
    assert fs.telemetry.resolver_hits >= 10


# ------------------------------------------------------- verify-on-hit
def test_cross_process_move_falls_back_via_verify(tmp_path):
    """Another process's flusher MOVEs the file cache→base without telling
    this resolver: the cached hit must verify-fail and re-scan, never
    return a dead path or stale data."""
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "moved.bin")
    fs.write_bytes(p, b"v1")
    assert fs.where(p) == "tmpfs"  # cached on the fast tier
    # simulate the external mover: copy to base, remove from cache
    src = os.path.join(cfg.tiers[0].roots[0], "moved.bin")
    dst = os.path.join(cfg.tiers[-1].roots[0], "moved.bin")
    shutil.copyfile(src, dst)
    os.remove(src)
    assert fs.read_bytes(p) == b"v1"
    assert fs.where(p) == "pfs"
    assert fs.telemetry.resolver_verify_fails >= 1


def test_external_delete_detected_by_verify(tmp_path):
    # window 0 = strict verify-on-hit: every hit lstats the cached path,
    # so even pure existence answers see the external delete immediately
    cfg = make_config(tmp_path, resolver_verify_window_s=0.0)
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "gone.bin")
    fs.write_bytes(p, b"z")
    os.remove(os.path.join(cfg.tiers[0].roots[0], "gone.bin"))
    assert not fs.exists(p)
    with pytest.raises(FileNotFoundError):
        fs.read_bytes(p)


# ------------------------------------------------------- negative cache
def test_negative_cache_absorbs_miss_storms(tmp_path):
    fs = SeaFS(make_config(tmp_path, resolver_negative_ttl_s=30.0))
    p = os.path.join(fs.mount, "nope.bin")
    assert not fs.exists(p)  # full scan, caches the negative
    with _CountingLocate(fs.hierarchy) as cl:
        for _ in range(10):
            assert not fs.exists(p)
        assert cl.calls == 0
    assert fs.telemetry.resolver_negative_hits >= 10


def test_negative_cache_expires_after_external_create(tmp_path):
    cfg = make_config(tmp_path, resolver_negative_ttl_s=0.05)
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "late.bin")
    assert not fs.exists(p)  # negative entry recorded
    # an external process creates the file directly on a root
    with open(os.path.join(cfg.tiers[-1].roots[0], "late.bin"), "wb") as f:
        f.write(b"here")
    time.sleep(0.06)  # > ttl
    assert fs.exists(p)
    assert fs.read_bytes(p) == b"here"


def test_open_never_spuriously_misses_through_negative_cache(tmp_path):
    """A fresh negative entry must not make open()/stat() raise ENOENT
    for a file another process created moments ago: the miss path does
    one authoritative scan before falling back."""
    cfg = make_config(tmp_path, resolver_negative_ttl_s=30.0)
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "racer.bin")
    assert not fs.exists(p)  # negative entry, trusted for 30s
    with open(os.path.join(cfg.tiers[0].roots[0], "racer.bin"), "wb") as f:
        f.write(b"just created")
    assert fs.read_bytes(p) == b"just created"  # open bypasses the negative
    assert fs.stat(p).st_size == len(b"just created")


def test_write_clears_negative_entry_immediately(tmp_path):
    fs = SeaFS(make_config(tmp_path, resolver_negative_ttl_s=30.0))
    p = os.path.join(fs.mount, "soon.bin")
    assert not fs.exists(p)  # negative cached for 30s
    fs.write_bytes(p, b"now")  # placement must overwrite the negative
    assert fs.exists(p)
    assert fs.read_bytes(p) == b"now"


# ------------------------------------------- invalidation on mutation paths
def test_remove_invalidates_and_removes_all_replicas(tmp_path):
    """COPY mode leaves a base replica next to the cache copy; remove()
    must take out both atomically (satellite: the seed probed per-tier)."""
    cfg = make_config(tmp_path, flushlist=("*.out",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "r.out")
    fs.write_bytes(p, b"r" * 32)
    fl.scan()
    fl._process_all_sync()
    # two replicas now: tmpfs (cache) + pfs (COPY flush)
    assert os.path.exists(os.path.join(cfg.tiers[0].roots[0], "r.out"))
    assert os.path.exists(os.path.join(cfg.tiers[-1].roots[0], "r.out"))
    fs.remove(p)
    for tier in cfg.tiers:
        for root in tier.roots:
            assert not os.path.exists(os.path.join(root, "r.out"))
    assert not fs.exists(p)
    assert fs.telemetry.resolver_invalidations >= 1


def test_remove_catches_multi_root_duplicates_on_one_tier(tmp_path):
    """A tier holding copies on two of its roots (external duplication):
    the seed's per-tier locate() removed only the first root's copy."""
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    for root in cfg.tiers[1].roots:  # both disk roots
        with open(os.path.join(root, "dup.bin"), "wb") as f:
            f.write(b"d")
    p = os.path.join(fs.mount, "dup.bin")
    fs.remove(p)
    for root in cfg.tiers[1].roots:
        assert not os.path.exists(os.path.join(root, "dup.bin"))


def test_rename_invalidates_source_and_notes_destination(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    a = os.path.join(fs.mount, "a.bin")
    b = os.path.join(fs.mount, "b.bin")
    fs.write_bytes(a, b"abc")
    fs.read_bytes(a)  # warm the index on the source
    fs.rename(a, b)
    assert not fs.exists(a)
    assert fs.read_bytes(b) == b"abc"


def test_stat_missing_file_names_mount_path(tmp_path):
    """Satellite: the FileNotFoundError must carry the user's path, not
    the translated base-tier path."""
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "absent/sub.bin")
    with pytest.raises(FileNotFoundError) as ei:
        fs.stat(p)
    assert ei.value.filename == p
    with pytest.raises(FileNotFoundError) as ei:
        fs.getsize(p)
    assert ei.value.filename == p
    base_root = fs.hierarchy.base.roots[0]
    assert base_root not in str(ei.value)


def test_remove_missing_file_names_mount_path(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "never.bin")
    with pytest.raises(FileNotFoundError) as ei:
        fs.remove(p)
    assert ei.value.filename == p


# ------------------------------------------------------- concurrent movers
def test_zero_stale_reads_under_concurrent_flusher_moves(tmp_path):
    """Writers produce MOVE-mode files while the async flusher migrates
    them cache→base and readers hammer resolution: every read must return
    the exact bytes written — no stale reads, no dead cached paths."""
    cfg = make_config(tmp_path, flushlist=("mv/*",), evictlist=("mv/*",))
    errors: list = []
    n_keys = 40
    with Sea(cfg) as sea:
        fs = sea.fs
        payloads = {}

        def writer():
            try:
                for i in range(n_keys):
                    data = bytes([i % 256]) * 128
                    p = os.path.join(fs.mount, f"mv/k{i}.bin")
                    fs.write_bytes(p, data)
                    payloads[i] = data
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def reader():
            try:
                for _ in range(200):
                    for i in list(payloads):
                        p = os.path.join(fs.mount, f"mv/k{i}.bin")
                        try:
                            got = fs.read_bytes(p)
                        except FileNotFoundError:
                            continue  # mid-move window is allowed to miss…
                        if got != payloads[i]:  # …but NEVER to be stale
                            errors.append(
                                AssertionError(f"stale read of k{i}: {got[:8]!r}")
                            )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader) for _ in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    # after drain every file lives exactly once, on the base tier
    fs2 = SeaFS(cfg)
    for i in range(n_keys):
        p = os.path.join(fs2.mount, f"mv/k{i}.bin")
        assert fs2.where(p) == "pfs"
        assert fs2.read_bytes(p) == bytes([i % 256]) * 128


def test_lru_eviction_invalidates_index(tmp_path):
    cfg = make_config(tmp_path, lru_evict=True, max_file_size=1 << 10, n_procs=1)
    cfg.tiers[0].capacity = 3 << 10
    cfg.tiers[1].capacity = 1
    fs = SeaFS(cfg)
    keys = ["a", "b", "c"]
    for k in keys:
        fs.write_bytes(os.path.join(fs.mount, f"{k}.bin"), k.encode() * 1024)
        fs.read_bytes(os.path.join(fs.mount, f"{k}.bin"))  # warm the index
    fs.write_bytes(os.path.join(fs.mount, "d.bin"), b"d" * 1024)
    # a was LRU-evicted: the index must not resurrect it
    assert fs.where(os.path.join(fs.mount, "a.bin")) is None
    assert fs.where(os.path.join(fs.mount, "d.bin")) == "tmpfs"


# ------------------------------------------------------- directory index
def _age_dirs(cfg, key: str, seconds: float = 10.0) -> None:
    """Backdate every tier copy of a virtual directory: freshly-mutated
    directories are deliberately not cached (same-mtime-tick races on
    coarse-granularity filesystems), stable ones are."""
    past = time.time() - seconds
    for tier in cfg.tiers:
        for root in tier.roots:
            p = os.path.join(root, key)
            if os.path.isdir(p):
                os.utime(p, (past, past))


def test_listdir_served_from_child_index(tmp_path):
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    for name in ("x.bin", "y.bin"):
        fs.write_bytes(os.path.join(fs.mount, "dir", name), b"1")
    _age_dirs(cfg, "dir")  # stable directory: eligible for the child index
    d = os.path.join(fs.mount, "dir")
    assert fs.listdir(d) == ["x.bin", "y.bin"]  # cold: walks + caches
    before = fs.telemetry.dir_index_hits
    assert fs.listdir(d) == ["x.bin", "y.bin"]  # warm: signature verifies
    assert fs.telemetry.dir_index_hits == before + 1


def test_fresh_directory_not_cached(tmp_path):
    """A directory mutated within the racy-mtime window must not enter
    the child index: a same-tick create would be invisible to the
    signature check."""
    fs = SeaFS(make_config(tmp_path))
    fs.write_bytes(os.path.join(fs.mount, "hot/a.bin"), b"a")
    d = os.path.join(fs.mount, "hot")
    assert fs.listdir(d) == ["a.bin"]
    assert fs.listdir(d) == ["a.bin"]  # still a walk, not an index hit
    assert fs.telemetry.dir_index_hits == 0


def test_invalidation_drops_parent_dir_listing(tmp_path):
    """An in-process mutation must invalidate ancestor dir listings
    immediately — not wait for the mtime signature to catch it."""
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "d/a.bin"), b"a")
    _age_dirs(cfg, "d")
    d = os.path.join(fs.mount, "d")
    assert fs.listdir(d) == ["a.bin"]
    assert fs.listdir(d) == ["a.bin"]  # cached now
    fs.remove(os.path.join(fs.mount, "d/a.bin"))
    # backdate again so a STALE cache entry would be served if the
    # invalidation had not dropped it
    _age_dirs(cfg, "d")
    assert fs.listdir(d) == []


def test_listdir_detects_external_create(tmp_path):
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "dir/a.bin"), b"a")
    d = os.path.join(fs.mount, "dir")
    assert fs.listdir(d) == ["a.bin"]
    # external process drops a file into another tier's root: the dir
    # mtime bump must fail the signature check and re-walk
    ext_dir = os.path.join(cfg.tiers[-1].roots[0], "dir")
    os.makedirs(ext_dir, exist_ok=True)
    with open(os.path.join(ext_dir, "b.bin"), "wb") as f:
        f.write(b"b")
    assert fs.listdir(d) == ["a.bin", "b.bin"]


def test_listdir_union_discards_ledger_dirname(tmp_path):
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "data.bin"), b"1")
    # the shared-ledger bookkeeping store lives inside a root
    os.makedirs(
        os.path.join(cfg.tiers[-1].roots[0], LEDGER_DIRNAME), exist_ok=True
    )
    listing = fs.listdir(fs.mount)
    assert LEDGER_DIRNAME not in listing
    assert "data.bin" in listing
    # …and stays discarded when served from the warm child index
    listing = fs.listdir(fs.mount)
    assert LEDGER_DIRNAME not in listing


def test_listdir_hides_inflight_flush_staging(tmp_path):
    """An in-flight flush stages to <dst>.sea_tmp before its atomic
    rename; the staging file must never leak into the listdir union."""
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "out/a.bin"), b"a")
    staging = os.path.join(cfg.tiers[-1].roots[0], "out")
    os.makedirs(staging, exist_ok=True)
    with open(os.path.join(staging, "a.bin.sea_tmp"), "wb") as f:
        f.write(b"partial")
    assert fs.listdir(os.path.join(fs.mount, "out")) == ["a.bin"]


def test_exists_and_isdir_for_virtual_directories(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    fs.write_bytes(os.path.join(fs.mount, "deep/nest/f.bin"), b"f")
    assert fs.exists(os.path.join(fs.mount, "deep"))
    assert fs.isdir(os.path.join(fs.mount, "deep/nest"))
    assert not fs.isdir(os.path.join(fs.mount, "deep/nest/f.bin"))
    assert not fs.isdir(os.path.join(fs.mount, "missing"))


# ------------------------------------------------------- flusher interplay
def test_flusher_move_then_read_returns_base_copy(tmp_path):
    cfg = make_config(tmp_path, flushlist=("*.out",), evictlist=("*.out",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "r.out")
    fs.write_bytes(p, b"r" * 32)
    fs.read_bytes(p)  # warm the index on the tmpfs copy
    fl.scan()
    fl._process_all_sync()  # MOVE: tmpfs copy gone, base copy exists
    assert fs.where(p) == "pfs"
    assert fs.read_bytes(p) == b"r" * 32
    assert fs.telemetry.resolver_invalidations >= 1


def test_prefetch_notes_staged_location(tmp_path):
    cfg = make_config(tmp_path, prefetchlist=("inputs/*",))
    base = cfg.tiers[-1].roots[0]
    os.makedirs(os.path.join(base, "inputs"), exist_ok=True)
    with open(os.path.join(base, "inputs/in.bin"), "wb") as f:
        f.write(b"i" * 64)
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    fl.prefetch()
    p = os.path.join(fs.mount, "inputs/in.bin")
    with _CountingLocate(fs.hierarchy) as cl:
        assert fs.read_bytes(p) == b"i" * 64
        # the only cascade allowed is the cold stripe-manifest existence
        # probe; the staged file itself was noted, no cascade for it
        assert cl.calls <= 1
    assert fs.where(p) == "tmpfs"
