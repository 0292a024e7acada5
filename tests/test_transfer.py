"""Data-plane tests: the chunked streaming TransferEngine and the five
copy sites routed through it (cross-mount rename, persist, flush,
prefetch, pipeline staging).

The crash-consistency tests drive the engine's fault-injection chunk
hook: a transfer killed at any chunk boundary must never leave a
partially-written destination visible to ``open``/``listdir``, must
clean up its ``.sea_tmp`` staging file, and must release every ledger
reservation it held.
"""

from __future__ import annotations

import os
import subprocess
import threading
import time

import pytest

from repro.core import (
    Sea,
    SeaConfig,
    SeaFS,
    TierSpec,
    TransferCancelled,
    TransferError,
)
from repro.core import transfer as transfer_mod

CHUNK = 64 << 10  # small chunks so every test file spans several


def make_config(tmp_path, **kw) -> SeaConfig:
    defaults = dict(
        mount=str(tmp_path / "mount"),
        tiers=[
            TierSpec(
                name="fast",
                roots=(str(tmp_path / "fast"),),
                capacity=kw.pop("fast_capacity", None),
            ),
            TierSpec(name="pfs", roots=(str(tmp_path / "pfs"),), persistent=True),
        ],
        max_file_size=1 << 20,
        transfer_chunk_bytes=CHUNK,
        transfer_retries=0,
        transfer_backoff_s=0.0,
    )
    defaults.update(kw)
    return SeaConfig(**defaults)


def tmp_files(*roots) -> list[str]:
    out = []
    for root in roots:
        for dirpath, _d, files in os.walk(root):
            out += [
                os.path.join(dirpath, f) for f in files if f.endswith(".sea_tmp")
            ]
    return out


class Boom(RuntimeError):
    pass


def kill_after(n_chunks: int):
    """Fault-injection hook: die after the n-th committed chunk."""
    state = {"n": 0}

    def hook(copied, total, tmp):
        state["n"] += 1
        if state["n"] >= n_chunks:
            raise Boom(f"injected crash at chunk {state['n']}")

    return hook


# ---------------------------------------------------------------- primitive
def test_copy_roundtrip_multichunk(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    src = tmp_path / "src.bin"
    data = os.urandom(CHUNK * 3 + 17)
    src.write_bytes(data)
    dst = tmp_path / "dst.bin"
    result = fs.transfer.copy(str(src), str(dst))
    assert dst.read_bytes() == data
    assert result.nbytes == len(data)
    assert result.attempts == 1
    assert result.impl in ("copy_file_range", "sendfile", "readwrite")


def test_copy_buffered_fallback(tmp_path, monkeypatch):
    """With both zero-copy syscalls unavailable the buffered loop must
    produce identical bytes."""
    monkeypatch.setattr(transfer_mod, "_HAS_COPY_FILE_RANGE", False)
    monkeypatch.setattr(transfer_mod, "_HAS_SENDFILE", False)
    fs = SeaFS(make_config(tmp_path))
    src = tmp_path / "src.bin"
    data = os.urandom(CHUNK * 2 + 5)
    src.write_bytes(data)
    result = fs.transfer.copy(str(src), str(tmp_path / "dst.bin"))
    assert result.impl == "readwrite"
    assert (tmp_path / "dst.bin").read_bytes() == data


def test_copy_retries_then_succeeds(tmp_path):
    cfg = make_config(tmp_path, transfer_retries=2)
    fs = SeaFS(cfg)
    src = tmp_path / "src.bin"
    src.write_bytes(os.urandom(CHUNK * 2))
    attempts = {"n": 0}

    def flaky(copied, total, tmp):
        if copied <= CHUNK and attempts["n"] < 2:
            attempts["n"] += 1
            raise Boom("transient")

    fs.transfer.chunk_hook = flaky
    result = fs.transfer.copy(str(src), str(tmp_path / "dst.bin"))
    assert result.attempts == 3
    assert (tmp_path / "dst.bin").read_bytes() == src.read_bytes()
    assert not tmp_files(str(tmp_path))


def test_copy_preserves_posix_error_class(tmp_path):
    """An OSError from the copy stage keeps its class/errno (the seed's
    bare shutil.copyfile surfaced IsADirectoryError etc. through rename
    and persist), and permanent errnos are not retried."""
    cfg = make_config(tmp_path, transfer_retries=5, transfer_backoff_s=0.1)
    fs = SeaFS(cfg)
    adir = tmp_path / "iamadir"
    adir.mkdir()
    t0 = time.perf_counter()
    with pytest.raises(IsADirectoryError):
        fs.transfer.copy(str(adir), str(tmp_path / "dst.bin"))
    # fail-fast: 5 retries at 0.1s doubling backoff would take >= 3s
    assert time.perf_counter() - t0 < 1.0
    assert not tmp_files(str(tmp_path))


def test_copy_failure_cleans_tmp_and_raises(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    src = tmp_path / "src.bin"
    src.write_bytes(os.urandom(CHUNK * 4))
    fs.transfer.chunk_hook = kill_after(2)
    with pytest.raises(TransferError):
        fs.transfer.copy(str(src), str(tmp_path / "dst.bin"))
    assert not (tmp_path / "dst.bin").exists()
    assert not tmp_files(str(tmp_path))


def test_cancellation_between_chunks(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    src = tmp_path / "src.bin"
    src.write_bytes(os.urandom(CHUNK * 16))
    started = threading.Event()

    def stall(copied, total, tmp):
        started.set()
        time.sleep(0.01)

    fs.transfer.chunk_hook = stall
    fut = fs.transfer.submit_copy(str(src), str(tmp_path / "dst.bin"))
    assert started.wait(5)
    fut.cancel()
    with pytest.raises(TransferCancelled):
        fut.result(timeout=10)
    assert not (tmp_path / "dst.bin").exists()
    assert not tmp_files(str(tmp_path))
    fs.transfer.close()


def test_bandwidth_throttle_paces_chunks(tmp_path):
    rate = 10e6  # 10 MB/s
    cfg = make_config(
        tmp_path, transfer_bandwidth_caps={"*": rate}, transfer_chunk_bytes=128 << 10
    )
    fs = SeaFS(cfg)
    src = tmp_path / "src.bin"
    src.write_bytes(os.urandom(1 << 20))
    t0 = time.perf_counter()
    fs.transfer.copy(str(src), str(tmp_path / "dst.bin"))
    elapsed = time.perf_counter() - t0
    # 1 MiB at 10 MB/s with a ~0.5 MB burst allowance: >= ~50ms of pacing
    assert elapsed >= 0.03, elapsed


# ---------------------------------------------------------- crash consistency
@pytest.mark.parametrize("workers", [1, 4])
def test_persist_crash_releases_reservation_no_partial(tmp_path, workers):
    cfg = make_config(tmp_path, transfer_workers=workers)
    sea = Sea(cfg)
    p = os.path.join(cfg.mount, "data/x.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(os.urandom(CHUNK * 4))
    base = sea.fs.hierarchy.base
    base_root = base.roots[0]
    sea.fs.transfer.chunk_hook = kill_after(2)
    with pytest.raises(TransferError):
        sea.fs.persist(p)
    sea.fs.transfer.chunk_hook = None
    # no partial destination visible through any read path
    assert not os.path.exists(os.path.join(base_root, "data/x.bin"))
    if os.path.isdir(os.path.join(base_root, "data")):
        assert "x.bin" not in os.listdir(os.path.join(base_root, "data"))
    assert not tmp_files(base_root)
    # the admission budget was returned and no ghost bytes were recorded
    assert base.reserved_bytes(base_root) == 0
    assert base.used_bytes(base_root) == 0
    # the source is intact and still readable through the mount
    with sea.fs.open(p, "rb") as f:
        assert len(f.read()) == CHUNK * 4


@pytest.mark.parametrize("workers", [1, 4])
def test_prefetch_crash_consistency(tmp_path, workers):
    """Killed staging transfers (pool path): no partial cache copy, no
    tmp leak, no reservation leak — and surviving keys still staged."""
    cfg = make_config(
        tmp_path,
        transfer_workers=workers,
        prefetchlist=("inputs/*",),
        fast_capacity=64 << 20,
    )
    sea = Sea(cfg)
    pfs = str(tmp_path / "pfs")
    for i in range(6):
        real = os.path.join(pfs, f"inputs/f{i}.bin")
        os.makedirs(os.path.dirname(real), exist_ok=True)
        with open(real, "wb") as f:
            f.write(os.urandom(CHUNK * 2))
    calls = {"n": 0}
    lock = threading.Lock()

    def sometimes(copied, total, tmp):
        with lock:
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                raise Boom("injected staging crash")

    sea.fs.transfer.chunk_hook = sometimes
    sea.flusher.prefetch()
    sea.fs.transfer.chunk_hook = None
    fast = sea.fs.hierarchy.tiers[0]
    fast_root = fast.roots[0]
    assert not tmp_files(fast_root, pfs)
    assert fast.reserved_bytes(fast_root) == 0
    # every file present in cache is complete; ledger matches the disk
    staged = 0
    for dirpath, _d, files in os.walk(fast_root):
        for fn in files:
            full = os.path.join(dirpath, fn)
            assert os.path.getsize(full) == CHUNK * 2
            staged += os.path.getsize(full)
    assert fast.used_bytes(fast_root) == staged


# ------------------------------------------------------------- rename paths
def test_rename_into_mount_atomic_commit(tmp_path):
    """Regression for the bare-copyfile cross-mount rename: the
    destination must never be visible half-written."""
    cfg = make_config(tmp_path)
    sea = Sea(cfg)
    ext = tmp_path / "outside.bin"
    data = os.urandom(CHUNK * 8)
    ext.write_bytes(data)
    dst = os.path.join(cfg.mount, "in.bin")
    roots = [r for t in sea.fs.hierarchy for r in t.roots]

    partial_sightings = []
    done = threading.Event()

    def watch():
        while not done.is_set():
            for root in roots:
                p = os.path.join(root, "in.bin")
                try:
                    size = os.path.getsize(p)
                except OSError:
                    continue
                if size != len(data):
                    partial_sightings.append(size)
            time.sleep(0.0005)

    sea.fs.transfer.chunk_hook = lambda *_a: time.sleep(0.003)
    t = threading.Thread(target=watch)
    t.start()
    try:
        sea.fs.rename(str(ext), dst)
    finally:
        done.set()
        t.join()
    sea.fs.transfer.chunk_hook = None
    assert partial_sightings == []
    assert not ext.exists()
    with sea.fs.open(dst, "rb") as f:
        assert f.read() == data


def test_rename_into_mount_crash_leaves_source(tmp_path):
    cfg = make_config(tmp_path)
    sea = Sea(cfg)
    ext = tmp_path / "outside.bin"
    ext.write_bytes(os.urandom(CHUNK * 4))
    dst = os.path.join(cfg.mount, "in.bin")
    sea.fs.transfer.chunk_hook = kill_after(2)
    with pytest.raises(TransferError):
        sea.fs.rename(str(ext), dst)
    sea.fs.transfer.chunk_hook = None
    assert ext.exists()  # move semantics: source only removed after commit
    assert not sea.fs.exists(dst)
    roots = [r for t in sea.fs.hierarchy for r in t.roots]
    assert not tmp_files(*roots)
    for t_ in sea.fs.hierarchy:
        for r in t_.roots:
            assert t_.reserved_bytes(r) == 0


def test_rename_missing_source_posix_error(tmp_path):
    cfg = make_config(tmp_path, fast_capacity=4 << 20, max_file_size=1 << 18)
    sea = Sea(cfg)
    for _ in range(3):
        with pytest.raises(FileNotFoundError):
            sea.fs.rename(
                str(tmp_path / "nope.bin"), os.path.join(sea.fs.mount, "x")
            )
    # the admission reservation taken for the destination must not leak
    # when the source turns out to be unreadable (repeated failed renames
    # would otherwise permanently exhaust a capped root's budget)
    fast = sea.fs.hierarchy.tiers[0]
    assert fast.reserved_bytes(fast.roots[0]) == 0


def test_rename_into_mount_drops_stale_slower_replica(tmp_path):
    """An inbound rename onto a key with a persisted base copy must not
    leave the old content to resurface after the cache copy is evicted."""
    cfg = make_config(tmp_path)
    sea = Sea(cfg)
    p = os.path.join(cfg.mount, "k.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(b"old" * 1000)
    sea.fs.persist(p)  # base replica now holds the old content
    ext = tmp_path / "new.bin"
    ext.write_bytes(b"new" * 2000)
    sea.fs.rename(str(ext), p)
    base_real = os.path.join(sea.fs.hierarchy.base.roots[0], "k.bin")
    assert not os.path.exists(base_real)  # stale base replica dropped
    # evicting the cache copy must not resurrect the old bytes
    fast = sea.fs.hierarchy.tiers[0]
    real = fast.locate("k.bin")
    assert real is not None
    with sea.fs.open(p, "rb") as f:
        assert f.read() == b"new" * 2000


def test_rename_into_mount_ledger_admission(tmp_path):
    """The destination root's ledger sees the renamed-in bytes (the seed
    recorded them only after the copy, with no in-flight reservation)."""
    cfg = make_config(tmp_path, fast_capacity=4 << 20, max_file_size=1 << 18)
    sea = Sea(cfg)
    ext = tmp_path / "outside.bin"
    ext.write_bytes(os.urandom(CHUNK * 3))
    sea.fs.rename(str(ext), os.path.join(cfg.mount, "in.bin"))
    fast = sea.fs.hierarchy.tiers[0]
    assert fast.used_bytes(fast.roots[0]) == CHUNK * 3
    assert fast.reserved_bytes(fast.roots[0]) == 0


def test_rename_out_of_mount_crash_keeps_sea_copy(tmp_path):
    cfg = make_config(tmp_path)
    sea = Sea(cfg)
    p = os.path.join(cfg.mount, "keep.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(os.urandom(CHUNK * 4))
    out = tmp_path / "exported.bin"
    sea.fs.transfer.chunk_hook = kill_after(2)
    with pytest.raises(TransferError):
        sea.fs.rename(p, str(out))
    sea.fs.transfer.chunk_hook = None
    assert not out.exists()
    assert sea.fs.exists(p)
    sea.fs.rename(p, str(out))  # now it works
    assert out.exists() and not sea.fs.exists(p)


def test_rename_out_creates_destination_dir(tmp_path):
    sea = Sea(make_config(tmp_path))
    p = os.path.join(sea.fs.mount, "exp.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(b"e" * 4096)
    out = tmp_path / "newdir" / "sub" / "exp.bin"  # parents don't exist yet
    sea.fs.rename(p, str(out))
    assert out.read_bytes() == b"e" * 4096


def test_flush_failure_counted_and_drain_raises(tmp_path):
    """A flush that exhausts its retries must not kill the worker thread,
    must be visible in telemetry, and a drain that ends with the file
    still unflushed must RAISE (shutdown durability contract)."""
    cfg = make_config(tmp_path, flushlist=("*",))
    sea = Sea(cfg)
    sea.flusher.start()
    sea.fs.transfer.chunk_hook = kill_after(1)
    p = os.path.join(cfg.mount, "doomed.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(b"d" * (CHUNK * 2))
    with pytest.raises(TransferError):
        sea.flusher.drain()
    assert sea.fs.telemetry.snapshot()["flush_failures"] >= 1
    # the worker survived: clearing the fault lets the flush succeed
    sea.fs.transfer.chunk_hook = None
    sea.flusher.drain()
    base_root = sea.fs.hierarchy.base.roots[0]
    assert os.path.exists(os.path.join(base_root, "doomed.bin"))
    sea.flusher.stop()


# ------------------------------------------------------------- flush freshness
def flush_and_read(sea, key):
    sea.flusher.process(key)
    base_root = sea.fs.hierarchy.base.roots[0]
    with open(os.path.join(base_root, key), "rb") as f:
        return f.read()


def test_flush_freshness_nanosecond_rewrite(tmp_path):
    """Regression for the coarse-mtime freshness check: a source
    rewritten within the same whole-second tick must still re-flush."""
    cfg = make_config(tmp_path, flushlist=("*",))
    sea = Sea(cfg)
    p = os.path.join(cfg.mount, "r.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(b"a" * 4096)
    key = sea.fs.key_of(p)
    assert flush_and_read(sea, key) == b"a" * 4096
    src_real = sea.fs.resolve_read(key)[1]
    dst_real = os.path.join(sea.fs.hierarchy.base.roots[0], key)
    # copystat parity: the committed base copy carries the source's mtime
    assert os.stat(dst_real).st_mtime_ns == os.stat(src_real).st_mtime_ns
    # rewrite the source 1ns later — a float-seconds getmtime compare
    # (the seed check) rounds this away and never re-flushes
    with sea.fs.open(p, "wb") as f:
        f.write(b"b" * 4096)
    st = os.stat(dst_real)
    os.utime(src_real, ns=(st.st_atime_ns, st.st_mtime_ns + 1))
    assert flush_and_read(sea, key) == b"b" * 4096


def test_flush_freshness_size_mismatch_same_mtime(tmp_path):
    """Same mtime but different size (clock stuck / coarse filesystem):
    the size compare must force the re-flush."""
    cfg = make_config(tmp_path, flushlist=("*",))
    sea = Sea(cfg)
    p = os.path.join(cfg.mount, "s.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(b"a" * 4096)
    key = sea.fs.key_of(p)
    flush_and_read(sea, key)
    with sea.fs.open(p, "wb") as f:
        f.write(b"c" * 8192)
    src_real = sea.fs.resolve_read(key)[1]
    dst_real = os.path.join(sea.fs.hierarchy.base.roots[0], key)
    st = os.stat(dst_real)
    os.utime(src_real, ns=(st.st_atime_ns, st.st_mtime_ns))  # identical mtime
    assert flush_and_read(sea, key) == b"c" * 8192


# ----------------------------------------------------------- orphan handling
def dead_pid() -> int:
    proc = subprocess.Popen(["true"])
    proc.wait()
    return proc.pid


def test_orphan_reaping_rules(tmp_path):
    from repro.core.transfer import _HOST

    fs = SeaFS(make_config(tmp_path))
    root = str(tmp_path / "fast")
    dead = os.path.join(root, f"a.bin.{_HOST}.{dead_pid()}.3.sea_tmp")
    alive = os.path.join(root, f"b.bin.{_HOST}.1.7.sea_tmp")  # pid 1 lives
    other_node = os.path.join(root, "d.bin.nodeX.1234.0.sea_tmp")
    fresh_unparseable = os.path.join(root, "c.bin.sea_tmp")
    for p in (dead, alive, other_node, fresh_unparseable):
        with open(p, "wb") as f:
            f.write(b"partial")
    assert fs.transfer.sweep_orphans(root) == 1
    assert not os.path.exists(dead)
    assert os.path.exists(alive)       # live pid on THIS host
    assert os.path.exists(other_node)  # foreign host: age grace only
    assert os.path.exists(fresh_unparseable)  # too young to condemn
    assert fs.telemetry.snapshot()["transfer_orphans_reaped"] == 1


def test_orphan_age_grace_reaps_stale_foreign_tmp(tmp_path):
    from repro.core import transfer as tm

    fs = SeaFS(make_config(tmp_path))
    root = str(tmp_path / "fast")
    stale = os.path.join(root, "e.bin.nodeX.1234.0.sea_tmp")
    with open(stale, "wb") as f:
        f.write(b"partial")
    old = time.time() - tm.ORPHAN_GRACE_S - 10
    os.utime(stale, (old, old))
    assert fs.transfer.maybe_reap_orphan(stale)
    assert not os.path.exists(stale)


def test_orphan_reap_rules_for_live_local_pid(tmp_path):
    """A live same-host pid protects a FRESH staging file (in-flight
    transfers keep their tmp mtime fresh), but not a stale one — the pid
    may have been recycled after the real owner crashed, and the dead
    bytes would otherwise occupy the root invisibly forever (capacity
    scans skip .sea_tmp)."""
    from repro.core import transfer as tm

    fs = SeaFS(make_config(tmp_path))
    root = str(tmp_path / "fast")
    fresh = os.path.join(root, f"f.bin.{tm._HOST}.{os.getpid()}.9.sea_tmp")
    stale = os.path.join(root, f"g.bin.{tm._HOST}.{os.getpid()}.10.sea_tmp")
    for p in (fresh, stale):
        with open(p, "wb") as f:
            f.write(b"partial")
    old = time.time() - tm.ORPHAN_GRACE_S - 10
    os.utime(stale, (old, old))
    assert not fs.transfer.maybe_reap_orphan(fresh)
    assert fs.transfer.maybe_reap_orphan(stale)
    assert os.path.exists(fresh) and not os.path.exists(stale)


def test_lru_walk_skips_inflight_tmp(tmp_path):
    """LRU room-making must never delete an in-flight staging file (and
    must not treat it as an evictable key)."""
    from repro.core.transfer import _HOST

    cfg = make_config(tmp_path, lru_evict=True)
    sea = Sea(cfg)
    fast_root = str(tmp_path / "fast")
    inflight = os.path.join(fast_root, f"live.bin.{_HOST}.1.0.sea_tmp")
    with open(inflight, "wb") as f:
        f.write(b"x" * 4096)
    for name in ("old.bin", "new.bin"):
        with sea.fs.open(os.path.join(cfg.mount, name), "wb") as f:
            f.write(b"o" * 8192)
    assert sea.fs._lru_make_room()  # evicted the closed KEEP-mode files
    assert not os.path.exists(os.path.join(fast_root, "old.bin"))
    assert os.path.exists(inflight)  # survived the LRU walk untouched


def test_flusher_scan_ignores_tmp_keys(tmp_path):
    cfg = make_config(tmp_path, flushlist=("*",))
    sea = Sea(cfg)
    fast_root = str(tmp_path / "fast")
    with open(os.path.join(fast_root, "ghost.bin.1.0.sea_tmp"), "wb") as f:
        f.write(b"partial")
    assert sea.flusher.scan() == 0
    base_root = sea.fs.hierarchy.base.roots[0]
    assert not tmp_files(base_root)


# -------------------------------------------------------------- prefetch pool
def test_prefetch_staged_bytes_accounted_and_admission_capped(tmp_path):
    """Prefetch staging reserves before copying: a capped cache tier can
    never be over-committed by concurrent staging, and staged bytes are
    ledger-visible."""
    n, size = 6, 64 << 10
    cap = int(2.5 * size) + (1 << 20)  # room for ~2 files + headroom
    cfg = make_config(
        tmp_path,
        prefetchlist=("inputs/*",),
        fast_capacity=cap,
        max_file_size=1 << 18,
        transfer_workers=4,
    )
    sea = Sea(cfg)
    pfs = str(tmp_path / "pfs")
    for i in range(n):
        real = os.path.join(pfs, f"inputs/f{i}.bin")
        os.makedirs(os.path.dirname(real), exist_ok=True)
        with open(real, "wb") as f:
            f.write(os.urandom(size))
    sea.flusher.prefetch()
    fast = sea.fs.hierarchy.tiers[0]
    fast_root = fast.roots[0]
    on_disk = sum(
        os.path.getsize(os.path.join(dp, fn))
        for dp, _d, files in os.walk(fast_root)
        for fn in files
    )
    assert on_disk <= cap
    assert fast.used_bytes(fast_root) == on_disk
    assert fast.reserved_bytes(fast_root) == 0
    assert sea.fs.telemetry.snapshot()["prefetched_bytes"] == on_disk


# ------------------------------------------------------------------ telemetry
def test_transfer_telemetry_pairs(tmp_path):
    cfg = make_config(tmp_path, flushlist=("*",))
    sea = Sea(cfg)
    p = os.path.join(cfg.mount, "t.bin")
    with sea.fs.open(p, "wb") as f:
        f.write(b"x" * (CHUNK + 1))
    key = sea.fs.key_of(p)
    sea.flusher.process(key)
    snap = sea.fs.telemetry.snapshot()
    assert snap["transfers"]["fast->pfs"]["nbytes"] == CHUNK + 1
    assert snap["transfers"]["fast->pfs"]["files"] == 1
    assert sea.fs.telemetry.transfer_rate_bps("fast->pfs") > 0

    from repro.core.telemetry import aggregate_snapshots

    agg = aggregate_snapshots([snap, snap])
    assert agg["transfers"]["fast->pfs"]["nbytes"] == 2 * (CHUNK + 1)


# ------------------------------------------------------------------- config
def test_config_validation():
    base = dict(
        mount="/tmp/sea_cfg_test/mount",
        tiers=[
            TierSpec(name="a", roots=("/tmp/sea_cfg_test/a",)),
            TierSpec(name="b", roots=("/tmp/sea_cfg_test/b",), persistent=True),
        ],
    )
    with pytest.raises(ValueError):
        SeaConfig(**base, transfer_workers=0)
    with pytest.raises(ValueError):
        SeaConfig(**base, transfer_chunk_bytes=0)
    with pytest.raises(ValueError):
        SeaConfig(**base, transfer_retries=-1)
    with pytest.raises(ValueError):
        SeaConfig(**base, transfer_bandwidth_caps={"a->b": 0})


# ------------------------------------------------------------------ simulator
def test_simulator_overlap_model_reduces_flush_tail():
    """More transfer workers must not lengthen the flush tail, and with a
    per-stream cap binding, overlap strictly shortens it."""
    from repro.core.model import ClusterSpec, MiB, Workload
    from repro.core.simulator import Simulator

    cl = ClusterSpec(c=1, p=2, g=1)
    w = Workload(n=4, F=256 * MiB, B=8)
    caps = {"*": 50e6}  # one stream alone cannot saturate the backend

    def tail(workers):
        sim = Simulator(
            cl, w, "sea-flushall",
            transfer_workers=workers, transfer_bandwidth_caps=caps,
        )
        return sim.run().makespan

    t1, t4 = tail(1), tail(4)
    assert t4 < t1
