"""Tests for the capacity-accounting ledger (O(1) placement hot path).

Covers the PR's acceptance criteria:
  * ledger matches a fresh os.walk after mixed create/overwrite/flush/
    evict/remove/rename traffic (1k random operations),
  * reservations prevent over-commit under concurrent writers,
  * reconciliation absorbs out-of-band file drops (external writers),
plus worker-pool flusher behaviour and the simulator's placement-cost
model.
"""

import os
import random
import threading
import time

import pytest

from repro.core import Sea, SeaConfig, SeaFS, TierSpec
from repro.core.flusher import Flusher
from repro.core.ledger import CapacityLedger, scan_root
from repro.core.tiers import Tier


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


def assert_ledger_matches_walk(fs):
    ledger = fs.hierarchy.ledger
    assert ledger is not None
    for tier in fs.hierarchy:
        for root in tier.roots:
            got, want = ledger.verify(root)
            assert got == want, f"{tier.name}:{root} ledger={got} walk={want}"


# ------------------------------------------------------------ unit behaviour
def test_ledger_basic_accounting(tmp_path):
    led = CapacityLedger(reconcile_interval_s=1e9)
    root = str(tmp_path)
    assert led.used_bytes(root) == 0
    led.note_written(root, "a.bin", 100)
    led.note_written(root, "b.bin", 50)
    assert led.used_bytes(root) == 150
    led.note_written(root, "a.bin", 10)  # overwrite: delta, not sum
    assert led.used_bytes(root) == 60
    led.note_removed(root, "b.bin")
    assert led.used_bytes(root) == 10
    led.note_removed(root, "b.bin")  # double-remove is a no-op
    assert led.used_bytes(root) == 10


def test_ledger_reservation_lifecycle(tmp_path):
    led = CapacityLedger(reconcile_interval_s=1e9)
    root = str(tmp_path)
    led.used_bytes(root)  # initial reconcile of the (empty) root
    res = led.reserve(root, 1000)
    assert led.reserved_bytes(root) == 1000
    led.commit(res, "x.bin", 640)
    assert led.reserved_bytes(root) == 0
    assert led.used_bytes(root) == 640
    # commit is idempotent on the reservation side
    led.commit(res, "x.bin", 640)
    assert led.reserved_bytes(root) == 0
    res2 = led.reserve(root, 500)
    led.release(res2)
    assert led.reserved_bytes(root) == 0
    assert led.used_bytes(root) == 640


def test_ledger_initial_reconcile_absorbs_preexisting_files(tmp_path):
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "old.bin").write_bytes(b"x" * 321)
    led = CapacityLedger(reconcile_interval_s=1e9)
    assert led.used_bytes(str(tmp_path)) == 321


def test_tier_free_bytes_is_ledger_backed(tmp_path):
    spec = TierSpec(name="t", roots=(str(tmp_path / "r"),), capacity=1 << 20)
    led = CapacityLedger(reconcile_interval_s=1e9)
    tier = Tier(spec, 0, led)
    root = tier.roots[0]
    assert tier.free_bytes(root) == 1 << 20
    tier.note_written(root, "f.bin", 1 << 10)
    assert tier.free_bytes(root) == (1 << 20) - (1 << 10)
    res = tier.reserve_write(root, 1 << 12)
    assert tier.free_bytes(root) == (1 << 20) - (1 << 10) - (1 << 12)
    tier.release_write(res)
    assert tier.free_bytes(root) == (1 << 20) - (1 << 10)


# ------------------------------------------------- consistency under traffic
def test_ledger_matches_walk_after_mixed_traffic(tmp_path):
    """1k random create/overwrite/remove/rename/flush/evict operations:
    the ledger must agree with a fresh filesystem walk at the end."""
    cfg = make_config(
        tmp_path,
        flushlist=("*.out",),
        evictlist=("*.out", "*.tmp"),
        ledger_reconcile_interval_s=1e9,  # no reconcile: pure delta tracking
    )
    # small capacities so traffic exercises spill across all three levels
    cfg.tiers[0].capacity = 1 << 18
    cfg.tiers[1].capacity = 1 << 19
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    rng = random.Random(1234)
    live: list[str] = []

    for step in range(1000):
        op = rng.random()
        if op < 0.45 or not live:
            name = f"d{rng.randrange(8)}/f{step}.{rng.choice(['bin', 'out', 'tmp'])}"
            p = os.path.join(fs.mount, name)
            fs.write_bytes(p, os.urandom(rng.randrange(1, 4096)))
            live.append(name)
        elif op < 0.65:
            name = rng.choice(live)
            fs.write_bytes(
                os.path.join(fs.mount, name), os.urandom(rng.randrange(1, 4096))
            )
        elif op < 0.80:
            name = live.pop(rng.randrange(len(live)))
            try:
                fs.remove(os.path.join(fs.mount, name))
            except FileNotFoundError:
                pass  # evicted (REMOVE-mode) by an earlier flusher pass
        elif op < 0.90:
            name = live.pop(rng.randrange(len(live)))
            new = f"mv{step}.bin"
            try:
                fs.rename(
                    os.path.join(fs.mount, name), os.path.join(fs.mount, new)
                )
                live.append(new)
            except FileNotFoundError:
                pass
        else:
            fl.scan()
            fl._process_all_sync()

    fl.scan()
    fl._process_all_sync()
    assert_ledger_matches_walk(fs)


def test_ledger_matches_walk_with_async_pool(tmp_path):
    """Same invariant with the real worker pool doing concurrent flushes."""
    cfg = make_config(
        tmp_path,
        flushlist=("out/*",),
        evictlist=("out/*", "*.tmp"),
        flush_workers=4,
        ledger_reconcile_interval_s=1e9,
    )
    with Sea(cfg) as sea:
        for i in range(40):
            sea.fs.write_bytes(
                os.path.join(sea.fs.mount, f"out/f{i}.bin"), os.urandom(256)
            )
            sea.fs.write_bytes(
                os.path.join(sea.fs.mount, f"s{i}.tmp"), os.urandom(64)
            )
            sea.fs.write_bytes(
                os.path.join(sea.fs.mount, f"keep{i}.bin"), os.urandom(128)
            )
    base = cfg.tiers[-1].roots[0]
    for i in range(40):
        assert os.path.exists(os.path.join(base, f"out/f{i}.bin"))
        assert not os.path.exists(os.path.join(base, f"s{i}.tmp"))
    assert_ledger_matches_walk(sea.fs)


# ------------------------------------------------------ reservation semantics
def test_reservation_prevents_overcommit_with_open_writers(tmp_path):
    """Files opened for write occupy 0 bytes on disk until data lands; the
    seed's stateless rescan let every concurrent open() see the same free
    space and over-commit a capped root. Reservations close that window."""
    F = 1 << 12
    cfg = make_config(tmp_path, max_file_size=F, n_procs=1)
    cfg.tiers[0].capacity = 4 * F
    fs = SeaFS(cfg)
    handles = []
    for i in range(4):
        handles.append(fs.open(os.path.join(fs.mount, f"w{i}.bin"), "wb"))
    # 4 in-flight reservations exhaust the tmpfs cap: the 5th must spill
    f5 = fs.open(os.path.join(fs.mount, "w4.bin"), "wb")
    assert fs.hierarchy.tiers[0].root_of(f5._real) is None
    for h in handles:
        h.write(b"x" * 16)
        h.close()
    f5.close()
    assert fs.where(os.path.join(fs.mount, "w0.bin")) == "tmpfs"
    assert fs.where(os.path.join(fs.mount, "w4.bin")) != "tmpfs"
    assert_ledger_matches_walk(fs)


def test_reservation_released_on_close_and_on_failed_open(tmp_path):
    F = 1 << 12
    cfg = make_config(tmp_path, max_file_size=F, n_procs=1)
    cfg.tiers[0].capacity = 4 * F
    fs = SeaFS(cfg)
    tier0 = fs.hierarchy.tiers[0]
    root0 = tier0.roots[0]
    f = fs.open(os.path.join(fs.mount, "a.bin"), "wb")
    assert tier0.reserved_bytes(root0) == F
    f.write(b"y" * 100)
    f.close()
    assert tier0.reserved_bytes(root0) == 0
    assert tier0.used_bytes(root0) == 100
    # invalid mode -> io.open raises -> reservation must be returned
    with pytest.raises(ValueError):
        fs.open(os.path.join(fs.mount, "b.bin"), "wb+q")
    assert tier0.reserved_bytes(root0) == 0


def test_concurrent_writers_never_overcommit_capped_root(tmp_path):
    """Many threads hammering a small capped root: committed bytes +
    reservations never exceed the cap at placement time. The tiny
    reconcile interval forces walks to race with commits — the ledger's
    version guard must discard those stale snapshots."""
    F = 1 << 10
    cfg = make_config(
        tmp_path, max_file_size=F, n_procs=1, ledger_reconcile_interval_s=0.01
    )
    cap = 8 * F
    cfg.tiers[0].capacity = cap
    fs = SeaFS(cfg)
    errs = []

    def work(i):
        try:
            for j in range(10):
                p = os.path.join(fs.mount, f"t{i}_{j}.bin")
                fs.write_bytes(p, os.urandom(F // 2))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    # the capped tmpfs root must never physically exceed its capacity
    tier0 = fs.hierarchy.tiers[0]
    assert sum(scan_root(tier0.roots[0]).values()) <= cap
    assert_ledger_matches_walk(fs)


def test_reservation_headroom_not_double_counted(tmp_path):
    """``n_procs * F`` is worst-case headroom for *untracked* writers;
    tracked reservations count toward it, not on top of it. Two concurrent
    writers on a 2F-capacity root with n_procs=2 provably fit and must
    BOTH land on the fast tier (the seed admitted both)."""
    F = 1 << 12
    cfg = make_config(tmp_path, max_file_size=F, n_procs=2)
    cfg.tiers[0].capacity = 2 * F
    fs = SeaFS(cfg)
    tier0 = fs.hierarchy.tiers[0]
    f1 = fs.open(os.path.join(fs.mount, "a.bin"), "wb")
    f2 = fs.open(os.path.join(fs.mount, "b.bin"), "wb")
    assert tier0.root_of(f1._real) is not None
    assert tier0.root_of(f2._real) is not None
    # a third concurrent writer would break used+reserved <= capacity
    f3 = fs.open(os.path.join(fs.mount, "c.bin"), "wb")
    assert tier0.root_of(f3._real) is None
    for h in (f1, f2, f3):
        h.write(b"z" * 8)
        h.close()
    assert_ledger_matches_walk(fs)


def test_flusher_defers_busy_reader_until_close(tmp_path):
    """A reader holding a file busy blocks its flush; the deferred flush
    must fire on that reader's close (a read close, which previously never
    re-submitted)."""
    cfg = make_config(tmp_path, flushlist=("*.out",), evictlist=("*.out",))
    fs = SeaFS(cfg)
    fl = Flusher(fs)
    p = os.path.join(fs.mount, "r.out")
    fs.write_bytes(p, b"r" * 32)   # close event queues the key
    f = fs.open(p, "rb")           # reader holds it busy
    fl._process_all_sync()
    assert fs.where(p) == "tmpfs"  # busy: deferred, not moved
    f.close()                      # read close re-submits the deferred key
    fl._process_all_sync()
    assert fs.where(p) == "pfs"


# ------------------------------------------------------------- reconciliation
def test_reconcile_absorbs_out_of_band_drops(tmp_path):
    cfg = make_config(tmp_path, ledger_reconcile_interval_s=1e9)
    cfg.tiers[0].capacity = 1 << 20
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "x.bin")
    fs.write_bytes(p, b"x" * 2048)
    tier0 = fs.hierarchy.tiers[0]
    root0 = tier0.roots[0]
    assert tier0.used_bytes(root0) == 2048
    # an external process deletes the file behind Sea's back
    os.remove(os.path.join(root0, "x.bin"))
    assert tier0.used_bytes(root0) == 2048  # ledger is (intentionally) stale
    fs.hierarchy.reconcile()
    assert tier0.used_bytes(root0) == 0
    assert_ledger_matches_walk(fs)


def test_stale_ledger_reconciles_automatically(tmp_path):
    cfg = make_config(tmp_path, ledger_reconcile_interval_s=0.05)
    cfg.tiers[0].capacity = 1 << 20
    fs = SeaFS(cfg)
    p = os.path.join(fs.mount, "x.bin")
    fs.write_bytes(p, b"x" * 1024)
    tier0 = fs.hierarchy.tiers[0]
    root0 = tier0.roots[0]
    # an external writer adds a file Sea never saw
    with open(os.path.join(root0, "alien.bin"), "wb") as f:
        f.write(b"a" * 512)
    time.sleep(0.06)  # exceed the staleness bound
    assert tier0.used_bytes(root0) == 1024 + 512
    assert fs.telemetry.ledger_reconciles >= 1


def test_ledger_telemetry_counters(tmp_path):
    cfg = make_config(tmp_path)
    cfg.tiers[0].capacity = 1 << 20
    fs = SeaFS(cfg)
    for i in range(5):
        fs.write_bytes(os.path.join(fs.mount, f"f{i}.bin"), b"z" * 64)
    snap = fs.telemetry.snapshot()
    assert snap["ledger_hits"] >= 5
    assert snap["ledger_reconciles"] >= 1  # the initial walk of the root


# ------------------------------------------------------------- simulator model
def test_simulator_models_stateless_placement_cost():
    """O(n)-per-decision placement (the seed) must cost strictly more than
    the O(1) ledger, and the gap must grow with iteration count."""
    from repro.core.model import ClusterSpec, MiB, Workload
    from repro.core.simulator import Simulator

    cl = ClusterSpec(c=1, p=2)
    mk = lambda n, **kw: Simulator(
        cl, Workload(B=8, F=64 * MiB, n=n), "sea", **kw
    ).run().makespan

    walk = dict(
        ledger_placement=False, placement_probe_s=1e-4,
        placement_scan_s_per_file=1e-3,
    )
    led = dict(
        ledger_placement=True, placement_probe_s=1e-4,
        placement_scan_s_per_file=1e-3,
    )
    gap_small = mk(4, **walk) - mk(4, **led)
    gap_big = mk(16, **walk) - mk(16, **led)
    assert gap_small > 0
    assert gap_big > gap_small * 2  # superlinear: more cached files per walk
