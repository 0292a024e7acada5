"""Extent-granular data plane: block-level placement, partially-staged
streaming reads, and extent-aware eviction.

Covers the three invariants the extent plane adds on top of PRs 1-5:

* a reader through a partial replica sees EXACTLY the base bytes, no
  matter which subset of extents is staged, punched, or in flight;
* the capacity ledger stays walk-consistent while sparse part files
  grow and shrink (``st_blocks`` accounting), so a file bigger than the
  cache tier streams through it without over-committing;
* the validity journal is crash-durable: a SIGKILL (or injected fault)
  at any chunk boundary leaves the mid-flight extent unmarked, never
  torn-but-valid, and a fresh process re-adopts the journal.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from repro.core import PART_SUFFIX, SeaConfig, SeaFS, SeaMount, TierSpec
from repro.core.extents import extent_token, journal_path, split_extent_token
from repro.core.ledger import scan_root

EXT = 128 << 10   # extent size: small, 4096-aligned (exact sparse accounting)
CHUNK = 16 << 10  # transfer chunk: several chunks per extent


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
        max_file_size=EXT,
        extent_map=True,
        extent_bytes=EXT,
        transfer_chunk_bytes=CHUNK,
        transfer_retries=0,
        transfer_backoff_s=0.0,
    )
    defaults.update(kw)
    return SeaConfig(**defaults)


def seed_base(fs: SeaFS, key: str, nbytes: int) -> bytes:
    """Place a file directly on the base tier (a cold PFS-resident input)."""
    data = os.urandom(nbytes)
    real = os.path.join(fs.hierarchy.base.roots[0], key)
    os.makedirs(os.path.dirname(real), exist_ok=True)
    with open(real, "wb") as f:
        f.write(data)
    return data


def part_files(root) -> list[str]:
    out = []
    for dirpath, _d, files in os.walk(root):
        out += [os.path.join(dirpath, f) for f in files if f.endswith(PART_SUFFIX)]
    return out


def ext_snap(fs: SeaFS) -> dict:
    return {k: v for k, v in fs.telemetry.snapshot().items() if "extent" in k}


def quiesce(fs: SeaFS, timeout: float = 10.0) -> None:
    """Stop the within-file readahead and wait out its in-flight staging
    jobs, so telemetry/ledger assertions are race-free."""
    fs.prefetcher.stop()
    deadline = time.time() + timeout
    while time.time() < deadline and fs.prefetcher._inflight > 0:
        time.sleep(0.01)


# ------------------------------------------------------------- read behaviour
def test_streaming_read_matches_base_and_promotes(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    data = seed_base(fs, "big.bin", 5 * EXT + 4096)
    p = os.path.join(fs.mount, "big.bin")
    with fs.open(p, "rb") as f:
        assert f.read() == data
    quiesce(fs)
    snap = ext_snap(fs)
    assert snap["extents_staged"] == 6
    assert snap["extent_staged_bytes"] == len(data)
    # every extent landed: the part file was promoted to a plain replica
    # and the journal retired — the key now resolves to the cache tier
    assert snap["extent_promotions"] == 1
    fast = fs.hierarchy.cache_tiers[0].roots[0]
    assert os.path.exists(os.path.join(fast, "big.bin"))
    assert not part_files(fast)
    assert not os.path.exists(journal_path(fast, "big.bin"))
    assert fs.where(p) == "fast"
    with fs.open(p, "rb") as f:
        assert f.read() == data


def test_random_access_stages_only_touched_extents(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    data = seed_base(fs, "r.bin", 8 * EXT)
    p = os.path.join(fs.mount, "r.bin")
    with fs.open(p, "rb") as f:
        for off in (0, 5 * EXT + 7, 2 * EXT + 100):
            f.seek(off)
            assert f.read(64) == data[off : off + 64]
    snap = ext_snap(fs)
    # only the touched extents (0, 5, 2 — plus at most readahead's
    # speculation) were staged, never the whole file
    assert 3 <= snap["extents_staged"] < 8
    fast = fs.hierarchy.cache_tiers[0].roots[0]
    assert part_files(fast)  # still partial: no promotion
    assert os.path.exists(journal_path(fast, "r.bin"))


def test_small_files_skip_the_extent_plane(tmp_path):
    """A file that fits one extent goes through the whole-file plane —
    no part file, no journal."""
    fs = SeaFS(make_config(tmp_path))
    data = seed_base(fs, "small.bin", EXT // 2)
    with fs.open(os.path.join(fs.mount, "small.bin"), "rb") as f:
        assert f.read() == data
    assert not part_files(fs.hierarchy.cache_tiers[0].roots[0])
    assert ext_snap(fs)["extents_staged"] == 0


def test_extent_map_off_never_creates_part_files(tmp_path):
    fs = SeaFS(make_config(tmp_path, extent_map=False))
    data = seed_base(fs, "w.bin", 4 * EXT)
    with fs.open(os.path.join(fs.mount, "w.bin"), "rb") as f:
        assert f.read() == data
    assert not part_files(fs.hierarchy.cache_tiers[0].roots[0])
    assert fs.extents is None


# ------------------------------------------------- capacity / ledger behaviour
def test_file_bigger_than_tier_streams_with_walk_consistent_ledger(tmp_path):
    cap = 4 * EXT
    fs = SeaFS(
        make_config(tmp_path, fast_capacity=cap, lru_evict=True)
    )
    data = seed_base(fs, "huge.bin", 16 * EXT)  # 4x the cache tier
    p = os.path.join(fs.mount, "huge.bin")
    with fs.open(p, "rb") as f:
        assert f.read() == data
    quiesce(fs)
    snap = ext_snap(fs)
    assert snap["extents_staged"] >= 16     # every extent passed through
    assert snap["extents_punched"] > 0      # cold blocks were punched out
    assert snap["extent_promotions"] == 0   # never whole on the tier
    tier = fs.hierarchy.cache_tiers[0]
    root = tier.roots[0]
    used = tier.used_bytes(root)
    assert used == sum(scan_root(root).values())  # ledger == the walk
    assert used <= cap
    # random access into a punched region re-faults correctly
    with fs.open(p, "rb") as f:
        f.seek(100)
        assert f.read(4096) == data[100 : 100 + 4096]
    assert tier.used_bytes(root) == sum(scan_root(root).values())


def test_getsize_and_stat_report_logical_size_while_partial(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    data = seed_base(fs, "s.bin", 6 * EXT)
    p = os.path.join(fs.mount, "s.bin")
    with fs.open(p, "rb") as f:
        f.read(100)  # stage only the first extent
    assert fs.getsize(p) == len(data)
    assert fs.stat(p).st_size == len(data)
    # the sparse part file itself also carries the logical size
    parts = part_files(fs.hierarchy.cache_tiers[0].roots[0])
    assert parts and os.stat(parts[0]).st_size == len(data)


def test_scan_used_bytes_counts_staged_blocks_not_holes(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    seed_base(fs, "h.bin", 8 * EXT)
    with fs.open(os.path.join(fs.mount, "h.bin"), "rb") as f:
        f.read(100)  # one extent staged, seven holes
    tier = fs.hierarchy.cache_tiers[0]
    root = tier.roots[0]
    scanned = sum(scan_root(root).values())
    staged = ext_snap(fs)["extent_staged_bytes"]
    assert staged < 8 * EXT  # partial by construction
    assert scanned == staged  # holes cost nothing; no double-count
    assert tier.used_bytes(root) == scanned


# ----------------------------------------------------------- crash consistency
def test_injected_fault_mid_extent_leaves_no_torn_valid(tmp_path):
    fs = SeaFS(make_config(tmp_path, fast_capacity=16 * EXT))
    data = seed_base(fs, "c.bin", 4 * EXT)
    p = os.path.join(fs.mount, "c.bin")

    calls = {"n": 0}

    def boom(copied, total, dst):
        calls["n"] += 1
        if calls["n"] >= 2:  # first chunk commits, then every attempt dies
            raise RuntimeError("injected crash")

    fs.transfer.chunk_hook = boom
    with fs.open(p, "rb") as f:
        got = f.read(100)
    # the reader FELL BACK to the base replica for the failed extent and
    # still produced exact bytes
    assert got == data[:100]
    em = fs.extents.get("c.bin")
    assert em is not None
    assert 0 not in em.valid  # the faulted extent was never marked valid
    # the admission reservation was released, not leaked
    tier = fs.hierarchy.cache_tiers[0]
    assert tier.reserved_bytes(tier.roots[0]) == 0
    assert tier.used_bytes(tier.roots[0]) == sum(scan_root(tier.roots[0]).values())
    # with the fault gone, a later read re-faults and heals the extent
    fs.transfer.chunk_hook = None
    with fs.open(p, "rb") as f:
        assert f.read() == data
    quiesce(fs)
    assert ext_snap(fs)["extent_promotions"] == 1  # fully staged in the end


def test_sigkill_mid_stage_journal_readoptable(tmp_path):
    """A process SIGKILLed between chunk commits of an extent stage must
    leave a journal a fresh process can trust: the in-flight extent is
    unmarked, every marked extent holds exact base bytes."""
    base = tmp_path / "pfs"
    base.mkdir()
    data = os.urandom(6 * EXT)
    (base / "k.bin").write_bytes(data)
    script = textwrap.dedent(
        f"""
        import os, signal
        from repro.core import SeaConfig, SeaFS, TierSpec
        cfg = SeaConfig(
            mount={str(tmp_path / "mount")!r},
            tiers=[
                TierSpec(name="fast", roots=({str(tmp_path / "fast")!r},)),
                TierSpec(name="pfs", roots=({str(base)!r},), persistent=True),
            ],
            max_file_size={EXT},
            extent_map=True,
            extent_bytes={EXT},
            transfer_chunk_bytes={CHUNK},
            transfer_retries=0,
        )
        fs = SeaFS(cfg)
        calls = {{"n": 0}}
        def hook(copied, total, dst):
            calls["n"] += 1
            if calls["n"] == {EXT // CHUNK + 3}:
                # two extents committed; die mid-chunk of the third
                os.kill(os.getpid(), signal.SIGKILL)
        fs.transfer.chunk_hook = hook
        with fs.open(os.path.join(fs.mount, "k.bin"), "rb") as f:
            f.read()
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd="/root/repo", env=env, timeout=60
    )
    assert proc.returncode == -signal.SIGKILL
    # the part file + journal survive; a fresh process re-adopts them
    fast = str(tmp_path / "fast")
    assert part_files(fast)
    fs2 = SeaFS(make_config(tmp_path))
    em = fs2.extents.load("k.bin", fs2.hierarchy.cache_tiers)
    assert em is not None
    assert em.valid  # the completed extents were journalled...
    part = part_files(fast)[0]
    with open(part, "rb") as f:
        for idx in sorted(em.valid):
            start, length = em.extent_range(idx)
            f.seek(start)
            assert f.read(length) == data[start : start + length]
    # ...and a full read through the adopted replica is exact
    with fs2.open(os.path.join(fs2.mount, "k.bin"), "rb") as f:
        assert f.read() == data


def test_stale_journal_dropped_when_base_rewritten(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    seed_base(fs, "m.bin", 4 * EXT)
    p = os.path.join(fs.mount, "m.bin")
    with fs.open(p, "rb") as f:
        f.read(100)
    assert fs.extents.get("m.bin") is not None
    # overwrite through the mount: the partial replica is stale
    new = os.urandom(3 * EXT)
    with fs.open(p, "wb") as f:
        f.write(new)
    assert fs.extents.get("m.bin") is None
    with fs.open(p, "rb") as f:
        assert f.read() == new


# ----------------------------------------------------------------- truncate
def test_truncate_updates_ledger_and_invalidates_extents(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    seed_base(fs, "t.bin", 4 * EXT)
    p = os.path.join(fs.mount, "t.bin")
    with fs.open(p, "rb") as f:
        f.read(100)  # create a partial replica
    assert fs.extents.get("t.bin") is not None
    fs.truncate(p, EXT)
    assert fs.extents.get("t.bin") is None  # extent state invalidated
    assert not part_files(fs.hierarchy.cache_tiers[0].roots[0])
    assert fs.getsize(p) == EXT
    base_tier = fs.hierarchy.base
    assert base_tier.used_bytes(base_tier.roots[0]) == sum(scan_root(base_tier.roots[0]).values())


def test_truncate_missing_key_raises_enoent(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    with pytest.raises(FileNotFoundError):
        fs.truncate(os.path.join(fs.mount, "nope.bin"), 0)


def test_ftruncate_settles_accounting_for_sea_fds(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "w.bin")
    with fs.open(p, "wb") as f:
        f.write(b"x" * (2 * EXT))
        f.flush()
        fs.ftruncate(f.fileno(), 4096)
    assert fs.getsize(p) == 4096
    tier, real = fs.resolver.resolve("w.bin")
    root = tier.root_of(real)
    assert tier.used_bytes(root) == sum(scan_root(root).values())


def test_os_truncate_intercepted_under_mount(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    seed_base(fs, "i.bin", 2 * EXT)
    p = os.path.join(fs.mount, "i.bin")
    outside = tmp_path / "outside.bin"
    outside.write_bytes(b"y" * 100)
    with SeaMount(fs):
        os.truncate(p, 4096)
        assert os.path.getsize(p) == 4096
        os.truncate(str(outside), 10)  # non-sea paths pass through
    assert outside.stat().st_size == 10
    assert fs.getsize(p) == 4096
    # restored after the context
    assert os.truncate is not None and fs.getsize(p) == 4096


# ------------------------------------------------------------------ readahead
def test_sequential_scan_predicts_extents(tmp_path):
    """A block-sequential scan feeds extent tokens to the stride
    detector; the predictor issues within-file readahead."""
    fs = SeaFS(make_config(tmp_path))
    data = seed_base(fs, "seq.bin", 10 * EXT)
    p = os.path.join(fs.mount, "seq.bin")
    with fs.open(p, "rb") as f:
        for _ in range(10):
            assert f.read(EXT)  # one extent per read
            time.sleep(0.01)   # let the digestion thread keep up
    deadline = time.time() + 5
    while time.time() < deadline:
        if fs.telemetry.snapshot()["readahead_predictions"] > 0:
            break
        time.sleep(0.05)
    assert fs.telemetry.snapshot()["readahead_predictions"] > 0
    fs.prefetcher.stop()


def test_extent_token_roundtrip():
    tok = extent_token("a/b/c_0012.npy", 7)
    assert split_extent_token(tok) == ("a/b/c_0012.npy", 7)
    assert split_extent_token("plain/key.npy") is None


# ----------------------------------------------------------------- namespace
def test_part_files_invisible_to_listdir_and_flusher(tmp_path):
    from repro.core import Sea

    sea = Sea(make_config(tmp_path, flushlist=("*",))).start()
    try:
        data = seed_base(sea.fs, "d/v.bin", 4 * EXT)
        p = os.path.join(sea.fs.mount, "d/v.bin")
        with sea.fs.open(p, "rb") as f:
            f.read(100)  # partial replica exists on the cache tier
        assert part_files(sea.fs.hierarchy.cache_tiers[0].roots[0])
        assert sea.fs.listdir(os.path.join(sea.fs.mount, "d")) == ["v.bin"]
        sea.flusher.scan()
        sea.flusher.drain()
        # the flusher never treated the part file as a flushable key
        assert not os.path.exists(
            os.path.join(
                sea.fs.hierarchy.base.roots[0], "d", "v.bin" + PART_SUFFIX
            )
        )
        with sea.fs.open(p, "rb") as f:
            assert f.read() == data
    finally:
        sea.shutdown()
