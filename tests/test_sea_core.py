"""Unit + property tests for the Sea core library (paper §3.1–3.3)."""

import os
import threading

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    Mode,
    Sea,
    SeaConfig,
    SeaFS,
    SeaMount,
    TierSpec,
    resolve_mode,
)
from repro.core.flusher import Flusher


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


# ---------------------------------------------------------------- mode table
@pytest.mark.parametrize(
    "flush,evict,expected",
    [
        (("*.out",), (), Mode.COPY),
        ((), ("*.out",), Mode.REMOVE),
        (("*.out",), ("*.out",), Mode.MOVE),
        ((), (), Mode.KEEP),
    ],
)
def test_mode_table(flush, evict, expected):
    """Table 1 of the paper."""
    assert resolve_mode("a/b/x.out", flush, evict) is expected


def test_mode_glob_full_path_and_basename():
    assert resolve_mode("results/iter9/x.npy", ("results/*/*.npy",), ()) is Mode.COPY
    assert resolve_mode("deep/nested/app.log", ("*.npy",), ("*.log",)) is Mode.REMOVE


# ------------------------------------------------------------ placement basics
def test_write_goes_to_fastest_tier(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "a/block.bin")
    fs.write_bytes(p, b"x" * 100)
    assert fs.where(p) == "tmpfs"
    assert fs.read_bytes(p) == b"x" * 100


def test_capacity_spills_to_next_tier(tmp_path):
    cfg = make_config(tmp_path)
    # tmpfs too small for the p*F reservation -> must go to disk
    cfg.tiers[0].capacity = 1 << 10
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "big.bin")
    fs.write_bytes(p, b"y" * 2048)
    assert fs.where(p) == "disk"


def test_capacity_spills_to_base_when_all_full(tmp_path):
    cfg = make_config(tmp_path)
    cfg.tiers[0].capacity = 1
    cfg.tiers[1].capacity = 1
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "big.bin")
    fs.write_bytes(p, b"z" * 4096)
    assert fs.where(p) == "pfs"


def test_reservation_accounts_nprocs_times_filesize(tmp_path):
    """Paper: tier eligible iff free >= n_procs * max_file_size."""
    cfg = make_config(tmp_path, max_file_size=1 << 12, n_procs=4)
    cfg.tiers[0].capacity = (1 << 12) * 3  # room for 3 files, need 4
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "f.bin")
    fs.write_bytes(p, b"q" * 16)
    assert fs.where(p) == "disk"


def test_rewrite_overwrites_in_place(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "f.bin")
    fs.write_bytes(p, b"1" * 8)
    tier0 = fs.where(p)
    fs.write_bytes(p, b"2" * 8)
    assert fs.where(p) == tier0
    assert fs.read_bytes(p) == b"2" * 8
    # exactly one physical copy exists
    copies = [t.locate("f.bin") for t in fs.hierarchy if t.locate("f.bin")]
    assert len(copies) == 1


def test_read_missing_raises_filenotfound(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    with pytest.raises(FileNotFoundError):
        fs.open(os.path.join(fs.mount, "nope.bin"), "rb")


def test_outside_mount_passthrough(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = str(tmp_path / "plain.txt")
    with fs.open(p, "w") as f:
        f.write("hi")
    assert os.path.exists(p)
    assert fs.telemetry.passthrough >= 1


# ------------------------------------------------------------ metadata ops
def test_listdir_union_across_tiers(tmp_path):
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "dir/a.bin"), b"a")
    # place b directly on pfs (simulates pre-existing input data)
    os.makedirs(os.path.join(cfg.tiers[-1].roots[0], "dir"), exist_ok=True)
    with open(os.path.join(cfg.tiers[-1].roots[0], "dir/b.bin"), "wb") as f:
        f.write(b"b")
    assert fs.listdir(os.path.join(fs.mount, "dir")) == ["a.bin", "b.bin"]


def test_rename_within_mount(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    a = os.path.join(fs.mount, "a.bin")
    b = os.path.join(fs.mount, "b.bin")
    fs.write_bytes(a, b"abc")
    fs.rename(a, b)
    assert not fs.exists(a)
    assert fs.read_bytes(b) == b"abc"


def test_stat_and_getsize(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "s.bin")
    fs.write_bytes(p, b"12345")
    assert fs.getsize(p) == 5
    assert fs.stat(p).st_size == 5


# ------------------------------------------------------------ flusher modes
def test_flush_copy_keeps_cache_copy(tmp_path):
    cfg = make_config(tmp_path, flushlist=("*.out",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "r.out")
    fs.write_bytes(p, b"r" * 32)
    fl.scan()
    fl._process_all_sync()
    # on base tier AND still in cache (COPY)
    assert os.path.exists(os.path.join(cfg.tiers[-1].roots[0], "r.out"))
    assert fs.where(p) == "tmpfs"


def test_flush_move_evicts_cache_copy(tmp_path):
    cfg = make_config(tmp_path, flushlist=("*.out",), evictlist=("*.out",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "r.out")
    fs.write_bytes(p, b"r" * 32)
    fl.scan()
    fl._process_all_sync()
    assert fs.where(p) == "pfs"  # only the persistent copy remains
    assert fs.read_bytes(p) == b"r" * 32


def test_evict_remove_never_persists(tmp_path):
    cfg = make_config(tmp_path, evictlist=("*.log",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "app.log")
    fs.write_bytes(p, b"l" * 32)
    fl.scan()
    fl._process_all_sync()
    assert fs.where(p) is None
    assert not os.path.exists(os.path.join(cfg.tiers[-1].roots[0], "app.log"))


def test_keep_stays_in_cache(tmp_path):
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "keep.bin")
    fs.write_bytes(p, b"k")
    fl.scan()
    fl._process_all_sync()
    assert fs.where(p) == "tmpfs"
    assert not os.path.exists(os.path.join(cfg.tiers[-1].roots[0], "keep.bin"))


def test_async_flusher_end_to_end(tmp_path):
    cfg = make_config(tmp_path, flushlist=("out/*",), evictlist=("out/*", "*.tmp"))
    with Sea(cfg) as sea:
        for i in range(8):
            sea.fs.write_bytes(os.path.join(sea.fs.mount, f"out/f{i}.bin"), b"d" * 64)
            sea.fs.write_bytes(os.path.join(sea.fs.mount, f"scratch_{i}.tmp"), b"t")
    base = cfg.tiers[-1].roots[0]
    for i in range(8):
        assert os.path.exists(os.path.join(base, f"out/f{i}.bin"))
        assert not os.path.exists(os.path.join(base, f"scratch_{i}.tmp"))


def test_flusher_skips_open_files(tmp_path):
    cfg = make_config(tmp_path, flushlist=("*.out",), evictlist=("*.out",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "busy.out")
    f = fs.open(p, "wb")
    f.write(b"partial")
    fl.submit("busy.out")
    fl._process_all_sync()
    # still open -> not moved
    assert fs.where(p) == "tmpfs"
    f.close()
    fl._process_all_sync()
    assert fs.where(p) == "pfs"


def test_prefetch_stages_inputs_to_cache(tmp_path):
    cfg = make_config(tmp_path, prefetchlist=("inputs/*",))
    # input data starts on the base tier (within the mountpoint, per paper)
    base = cfg.tiers[-1].roots[0]
    os.makedirs(os.path.join(base, "inputs"), exist_ok=True)
    for i in range(3):
        with open(os.path.join(base, f"inputs/in{i}.bin"), "wb") as f:
            f.write(b"i" * 128)
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    n = fl.prefetch()
    assert n == 3 * 128
    for i in range(3):
        assert fs.where(os.path.join(fs.mount, f"inputs/in{i}.bin")) == "tmpfs"


# ------------------------------------------------------------ interception
def test_seamount_redirects_builtin_open(tmp_path):
    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "inter/x.txt")
    with SeaMount(fs):
        with open(p, "w") as f:
            f.write("hello sea")
        assert os.path.exists(p)
        assert os.path.getsize(p) == 9
        with open(p) as f:
            assert f.read() == "hello sea"
    # the physical file lives on a tier, not under the mountpoint
    assert not os.path.exists(p)
    assert fs.where(p) == "tmpfs"


def test_seamount_numpy_roundtrip(tmp_path):
    """Unmodified numpy code works through interception (reinstrumentation-
    free, the paper's core claim)."""
    import numpy as np

    cfg = make_config(tmp_path)
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "arr.npy")
    arr = np.arange(100, dtype=np.int32)
    with SeaMount(fs):
        np.save(p, arr)
        out = np.load(p)
    assert (out == arr).all()
    assert fs.where(p) == "tmpfs"


def test_seamount_restores_builtins(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    orig_open = open
    with SeaMount(fs):
        assert open is not orig_open
    import builtins

    assert builtins.open is orig_open


def test_seamount_isfile_false_for_directories(tmp_path):
    """Tier.locate uses lexists (true for dirs): patched os.path.isfile must
    still report False for directories under the mount."""
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "d/f.txt")
    with SeaMount(fs):
        with open(p, "w") as f:
            f.write("z")
        assert os.path.isfile(p)
        assert not os.path.isfile(os.path.dirname(p))
        assert not os.path.isfile(os.path.join(fs.mount, "missing.txt"))


def test_seamount_handler_errors_propagate(tmp_path):
    """A legitimate error raised by the Sea handler must propagate, not be
    swallowed by the probe guard and silently re-executed on the original."""
    fs = SeaFS(make_config(tmp_path))
    sm = SeaMount(fs)

    def boom(path, *a, **kw):
        raise ValueError("sea handler failure")

    wrapped = sm._path_fn(lambda p, *a, **kw: "orig-ran", boom)
    with pytest.raises(ValueError, match="sea handler failure"):
        wrapped(os.path.join(fs.mount, "x"))
    # outside the mount the original still runs
    assert wrapped(str(tmp_path / "plain")) == "orig-ran"

    def boom2(src, dst, *a, **kw):
        raise ValueError("sea two-path failure")

    wrapped2 = sm._two_path_fn(lambda s, d, *a, **kw: "orig-ran", boom2)
    with pytest.raises(ValueError, match="sea two-path failure"):
        wrapped2(os.path.join(fs.mount, "a"), os.path.join(fs.mount, "b"))
    assert wrapped2(str(tmp_path / "p"), str(tmp_path / "q")) == "orig-ran"


def test_seamount_os_ops(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "d/f.txt")
    q = os.path.join(fs.mount, "d/g.txt")
    with SeaMount(fs):
        with open(p, "w") as f:
            f.write("z")
        assert os.path.isfile(p)
        os.replace(p, q)
        assert not os.path.exists(p)
        assert sorted(os.listdir(os.path.dirname(p))) == ["g.txt"]
        os.remove(q)
        assert not os.path.exists(q)


# ------------------------------------------------------------ concurrency
def test_concurrent_writers_thread_safe(tmp_path):
    cfg = make_config(tmp_path, n_procs=8)
    fs = SeaFS(cfg)
    errs = []

    def work(i):
        try:
            for j in range(20):
                p = os.path.join(fs.mount, f"w{i}/f{j}.bin")
                fs.write_bytes(p, bytes([i]) * 256)
                assert fs.read_bytes(p) == bytes([i]) * 256
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs


# ------------------------------------------------------------ property tests
@settings(max_examples=50, deadline=None)
@given(
    data=st.binary(min_size=0, max_size=4096),
    name=st.from_regex(r"[a-z]{1,8}(/[a-z]{1,8}){0,2}\.(bin|out|log)", fullmatch=True),
)
def test_roundtrip_property(tmp_path_factory, data, name):
    """Whatever Sea places anywhere, reads return identical bytes and the
    file exists on exactly one tier (paper: 'In no instance does it modify
    or alter the data')."""
    tmp_path = tmp_path_factory.mktemp("prop")
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, name)
    fs.write_bytes(p, data)
    assert fs.read_bytes(p) == data
    key = fs.key_of(p)
    copies = [t for t in fs.hierarchy if t.locate(key)]
    assert len(copies) == 1


@settings(max_examples=100, deadline=None)
@given(
    rel=st.from_regex(r"[a-z]{1,6}(/[a-z]{1,6}){0,3}\.[a-z]{1,4}", fullmatch=True),
    flush=st.booleans(),
    evict=st.booleans(),
)
def test_mode_resolution_total_function(rel, flush, evict):
    """Mode resolution is total and matches Table 1 for any path."""
    fl = (rel,) if flush else ()
    ev = (rel,) if evict else ()
    m = resolve_mode(rel, fl, ev)
    expected = {
        (True, True): Mode.MOVE,
        (True, False): Mode.COPY,
        (False, True): Mode.REMOVE,
        (False, False): Mode.KEEP,
    }[(flush, evict)]
    assert m is expected


def test_lru_evict_makes_room(tmp_path):
    cfg = make_config(tmp_path, lru_evict=True, max_file_size=1 << 10, n_procs=1)
    cfg.tiers[0].capacity = 3 << 10
    cfg.tiers[1].capacity = 1  # disk unusable: spill would go to pfs
    fs = SeaFS(cfg)
    a = os.path.join(fs.mount, "a.bin")
    b = os.path.join(fs.mount, "b.bin")
    c = os.path.join(fs.mount, "c.bin")
    fs.write_bytes(a, b"a" * 1024)
    fs.write_bytes(b, b"b" * 1024)
    fs.write_bytes(c, b"c" * 1024)  # tmpfs now at capacity
    d = os.path.join(fs.mount, "d.bin")
    fs.write_bytes(d, b"d" * 1024)
    # LRU(a) was evicted to make room; d landed on tmpfs
    assert fs.where(d) == "tmpfs"
    assert fs.where(a) is None


# ------------------------------------------------------------ striping (§6)
def test_striped_write_spreads_across_roots(tmp_path):
    """Paper §6 future work: file splitting across same-level devices."""
    cfg = make_config(tmp_path, stripe_chunk_bytes=1 << 10)
    fs = SeaFS(cfg)
    # force placement past tmpfs so the 2-root disk level stripes
    cfg.tiers[0].capacity = 1
    p = os.path.join(fs.mount, "big.bin")
    data = bytes(range(256)) * 24  # 6 KiB -> 6 parts over 2 roots
    fs.write_bytes(p, data)
    assert fs.read_bytes(p) == data
    import glob as _glob

    d0 = _glob.glob(str(tmp_path / "d0" / "*.sea_stripe.0*"))
    d1 = _glob.glob(str(tmp_path / "d1" / "*.sea_stripe.0*"))
    assert len(d0) == 3 and len(d1) == 3  # round-robin across both disks


def test_striped_roundtrip_property(tmp_path):
    cfg = make_config(tmp_path, stripe_chunk_bytes=512)
    cfg.tiers[0].capacity = 1
    fs = SeaFS(cfg)
    for size in (0, 1, 511, 512, 513, 4096, 5000):
        p = os.path.join(fs.mount, f"s{size}.bin")
        data = os.urandom(size)
        fs.write_bytes(p, data)
        assert fs.read_bytes(p) == data, size


def test_striped_write_crash_leaves_no_partial_part(tmp_path, monkeypatch):
    """A failure mid-stripe must never leave a short part under a
    resolvable stripe name: parts commit via tmp + os.replace, so the
    torn write exists only as a .sea_tmp staging orphan (seacheck
    atomic-commit invariant)."""
    import glob as _glob

    import repro.core.seafs as seafs_mod

    cfg = make_config(tmp_path, stripe_chunk_bytes=512)
    cfg.tiers[0].capacity = 1
    fs = SeaFS(cfg)
    real_replace = os.replace
    calls = {"n": 0}

    def exploding_replace(src, dst, *a, **kw):
        if ".sea_stripe." in str(dst):
            calls["n"] += 1
            if calls["n"] == 2:  # part 0 commits; part 1 "crashes"
                raise OSError(5, "injected crash")
        return real_replace(src, dst, *a, **kw)

    monkeypatch.setattr(seafs_mod.os, "replace", exploding_replace)
    p = os.path.join(fs.mount, "crash.bin")
    with pytest.raises(OSError):
        fs.write_bytes(p, os.urandom(2048))
    visible = [
        f
        for f in _glob.glob(str(tmp_path / "*" / "*.sea_stripe.*"))
        if ".sea_tmp" not in f
    ]
    assert calls["n"] == 2
    # every part that became resolvable is a COMPLETE chunk; the torn
    # one never appeared under its stripe name
    assert visible and all(os.path.getsize(f) == 512 for f in visible)


def test_striping_disabled_is_whole_file(tmp_path):
    fs = SeaFS(make_config(tmp_path))  # stripe_chunk_bytes=0
    p = os.path.join(fs.mount, "w.bin")
    fs.write_bytes(p, b"x" * 4096)
    assert fs.where(p) == "tmpfs"
    import glob as _glob

    assert not _glob.glob(str(tmp_path / "*" / "*.sea_stripe.0*"))


# ------------------------------------------------------------- config files
#: non-default values for the str fields (bool, int and float values are
#: derived from the defaults); mount is required and set separately
_STR_VALUES = {
    "faults": "transfer.chunk:errno=EIO,p=0.5,n=3",
    "federation_node": "node-a",
}


def _non_default(f):
    if f.type == "bool":
        return not f.default
    if f.type == "int":
        return f.default + 1
    if f.type == "float":
        return f.default + 0.25
    return _STR_VALUES[f.name]


@pytest.mark.parametrize("kind", ["bool", "int", "float", "str"])
def test_from_file_reads_every_field(tmp_path, kind):
    """Every scalar field of one declared type, set to a valid non-default
    value in ``[sea]``, comes back from ``from_file`` as exactly that
    value; every other field keeps its dataclass default."""
    import dataclasses

    fields = [
        f for f in dataclasses.fields(SeaConfig) if f.type == kind and f.name != "mount"
    ]
    assert fields
    if kind == "str":
        assert {f.name for f in fields} == set(_STR_VALUES)
    want = {f.name: _non_default(f) for f in fields}
    lines = ["[sea]", f"mount = {tmp_path / 'mount'}"]
    lines += [f"{k} = {v!r}" if kind == "float" else f"{k} = {v}" for k, v in want.items()]
    lines += ["[tier.fast]", f"roots = {tmp_path / 't0'}"]
    lines += ["[tier.pfs]", f"roots = {tmp_path / 'pfs'}", "persistent = true"]
    ini = tmp_path / "sea.ini"
    ini.write_text("\n".join(lines) + "\n")
    cfg = SeaConfig.from_file(str(ini))
    assert {name: getattr(cfg, name) for name in want} == want
    for f in dataclasses.fields(SeaConfig):
        if f.name not in want and f.name != "mount" and f.type in ("bool", "int", "float", "str"):
            assert getattr(cfg, f.name) == f.default, f.name
    assert cfg.mount == str(tmp_path / "mount")
    assert [t.name for t in cfg.tiers] == ["fast", "pfs"] and cfg.tiers[-1].persistent
