"""Tests for the cross-process shared capacity ledger + flusher election.

Covers this PR's acceptance criteria:
  * 8 concurrent *processes* writing into a capped root never over-commit
    it (walk-verified after drain),
  * exactly one live flusher daemon per hierarchy,
  * follower takeover within 2 heartbeats when the leader is SIGKILLed,
  * orphaned reservations of dead PIDs are expired on reconcile,
plus journal mechanics (compaction, torn-record repair), the follower
spool, idempotent ``Sea.start``, leadership release on failing ``stop()``,
per-process telemetry aggregation, and the simulator's contention model.
"""

import json
import multiprocessing as mp
import os
import signal
import time

import pytest

from repro.core import Sea, SeaConfig, SeaFS, TierSpec
from repro.core.ledger import LEDGER_DIRNAME, scan_root
from repro.core.shared_ledger import SharedCapacityLedger, pid_alive
from repro.core.telemetry import Telemetry, aggregate_snapshots, load_aggregate

F = 1 << 12  # 4 KiB "max file size" used throughout

_mp = mp.get_context("fork")


def make_config(workdir: str, **kw) -> SeaConfig:
    defaults = dict(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(
                name="tmpfs", roots=(os.path.join(workdir, "t0"),), capacity=16 * F
            ),
            TierSpec(name="pfs", roots=(os.path.join(workdir, "pfs"),), persistent=True),
        ],
        max_file_size=F,
        n_procs=8,
        shared_ledger=True,
        leader_heartbeat_s=0.2,
        ledger_reconcile_interval_s=1e9,  # isolate delta tracking from walks
    )
    defaults.update(kw)
    return SeaConfig(**defaults)


def _heartbeat_path(cfg: SeaConfig) -> str:
    return os.path.join(cfg.tiers[-1].roots[0], LEDGER_DIRNAME, "flusher.heartbeat")


def _read_heartbeat_pid(cfg: SeaConfig) -> int | None:
    try:
        with open(_heartbeat_path(cfg)) as f:
            return json.load(f).get("pid")
    except (OSError, ValueError):
        return None


def _walk_used(root: str) -> int:
    total = 0
    for dirpath, dirnames, files in os.walk(root):
        if LEDGER_DIRNAME in dirnames:
            dirnames.remove(LEDGER_DIRNAME)
        for fn in files:
            total += os.path.getsize(os.path.join(dirpath, fn))
    return total


# --------------------------------------------------------- subprocess workers
def _accounting_child(workdir: str) -> None:
    fs = SeaFS(make_config(workdir))
    fs.write_bytes(os.path.join(fs.mount, "from_child.bin"), b"c" * 700)


def _hammer_worker(workdir: str, idx: int, barrier, leader_flags) -> None:
    """One of 8 processes hammering the capped root through its own Sea."""
    cfg = make_config(workdir, flushlist=("*.out",), evictlist=("*.out",))
    sea = Sea(cfg).start()
    barrier.wait(timeout=30)  # everyone runs concurrently
    leader_flags[idx] = 1 if sea.flusher.is_leader else 0
    for j in range(12):
        data = os.urandom(F if j % 3 else F // 2)
        suffix = "out" if j % 4 == 0 else "bin"
        sea.fs.write_bytes(
            os.path.join(sea.fs.mount, f"w{idx}_{j}.{suffix}"), data
        )
    barrier.wait(timeout=30)  # hold leadership until everyone sampled/wrote
    sea.shutdown()


def _leader_candidate(workdir: str, ready, stop) -> None:
    cfg = make_config(workdir, leader_heartbeat_s=0.75)
    Sea(cfg).start()
    ready.set()
    while not stop.is_set():
        time.sleep(0.02)


def _orphan_reserver(workdir: str, root: str) -> None:
    led = SharedCapacityLedger(reconcile_interval_s=1e9)
    led.reserve(root, 12345)
    os._exit(0)  # die without releasing: the reservation is orphaned


# ------------------------------------------------------ cross-process ledger
def test_shared_ledger_cross_process_accounting(tmp_path):
    wd = str(tmp_path)
    fs = SeaFS(make_config(wd))
    fs.write_bytes(os.path.join(fs.mount, "from_parent.bin"), b"p" * 300)
    proc = _mp.Process(target=_accounting_child, args=(wd,))
    proc.start()
    proc.join(timeout=60)
    assert proc.exitcode == 0
    tier0 = fs.hierarchy.tiers[0]
    root0 = tier0.roots[0]
    # the parent's ledger replica sees the child's write without a re-walk
    # (reconcile interval is 1e9 s — only journal replay can surface it).
    # used_bytes has a documented advisory staleness of hint_window_s
    # (50 ms): on a fast machine the child finishes inside the parent's
    # hint window, so poll past it instead of racing it.
    deadline = time.monotonic() + 5
    while tier0.used_bytes(root0) != 1000 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert tier0.used_bytes(root0) == 300 + 700
    got, want = fs.hierarchy.ledger.verify(root0)
    assert got == want == 1000


@pytest.mark.slow
def test_eight_processes_never_overcommit_and_one_flusher(tmp_path):
    """The PR's acceptance scenario: 8 real processes, one capped root."""
    wd = str(tmp_path)
    n_procs = 8
    barrier = _mp.Barrier(n_procs)
    leader_flags = _mp.Array("i", [0] * n_procs)
    procs = [
        _mp.Process(target=_hammer_worker, args=(wd, i, barrier, leader_flags))
        for i in range(n_procs)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
    assert all(p.exitcode == 0 for p in procs)
    cfg = make_config(wd)
    capacity = cfg.tiers[0].capacity
    cache_root = cfg.tiers[0].roots[0]
    # walk-verified: the capped root physically holds at most its capacity
    used = _walk_used(cache_root)
    assert used <= capacity, f"over-committed: {used} > {capacity}"
    # exactly one flusher daemon was leader while all 8 ran concurrently
    assert sum(leader_flags) == 1, list(leader_flags)
    # every write landed somewhere (cache or spilled to base) — none lost
    fs = SeaFS(make_config(wd))
    for i in range(n_procs):
        for j in range(12):
            suffix = "out" if j % 4 == 0 else "bin"
            assert fs.exists(os.path.join(fs.mount, f"w{i}_{j}.{suffix}"))
    # after every Sea drained, no orphaned reservations remain
    fs.hierarchy.reconcile()
    assert fs.hierarchy.tiers[0].reserved_bytes(cache_root) == 0


@pytest.mark.slow
def test_leader_failover_within_two_heartbeats_on_sigkill(tmp_path):
    wd = str(tmp_path)
    hb = 0.75
    cfg = make_config(wd, leader_heartbeat_s=hb)
    ready_a, ready_b = _mp.Event(), _mp.Event()
    stop = _mp.Event()
    a = _mp.Process(target=_leader_candidate, args=(wd, ready_a, stop))
    a.start()
    assert ready_a.wait(timeout=30)
    deadline = time.time() + 10
    while _read_heartbeat_pid(cfg) != a.pid and time.time() < deadline:
        time.sleep(0.05)
    assert _read_heartbeat_pid(cfg) == a.pid
    b = _mp.Process(target=_leader_candidate, args=(wd, ready_b, stop))
    b.start()
    assert ready_b.wait(timeout=30)
    time.sleep(2 * hb)  # give B time to (wrongly) steal — it must not
    assert _read_heartbeat_pid(cfg) == a.pid
    os.kill(a.pid, signal.SIGKILL)
    a.join(timeout=30)
    t_kill = time.time()
    while _read_heartbeat_pid(cfg) != b.pid and time.time() - t_kill < 10:
        time.sleep(0.02)
    elapsed = time.time() - t_kill
    assert _read_heartbeat_pid(cfg) == b.pid, "follower never took over"
    assert elapsed <= 2 * hb, f"takeover took {elapsed:.2f}s > 2 heartbeats"
    stop.set()
    b.join(timeout=30)


def test_orphaned_reservation_expired_on_reconcile(tmp_path):
    root = str(tmp_path / "r")
    os.makedirs(root)
    led = SharedCapacityLedger(reconcile_interval_s=1e9)
    proc = _mp.Process(target=_orphan_reserver, args=(str(tmp_path), root))
    proc.start()
    proc.join(timeout=60)
    assert not pid_alive(proc.pid)
    assert led.reserved_bytes(root) == 12345  # orphan budget still charged
    led.reconcile(root)
    assert led.reserved_bytes(root) == 0  # crash recovery returned it
    # a live process's reservation must survive the same reconcile
    res = led.reserve(root, 777)
    led.reconcile(root)
    assert led.reserved_bytes(root) == 777
    led.release(res)


def test_two_instances_same_process_reservations_do_not_alias(tmp_path):
    """Two ledger instances in one process must mint distinct reservation
    markers — aliasing would merge (then double-free) their budgets."""
    root = str(tmp_path / "r")
    os.makedirs(root)
    a = SharedCapacityLedger(reconcile_interval_s=1e9)
    b = SharedCapacityLedger(reconcile_interval_s=1e9)
    ra = a.reserve(root, 100)
    rb = b.reserve(root, 200)
    assert ra.path != rb.path
    assert a.reserved_bytes(root) == 300
    a.release(ra)
    assert b.reserved_bytes(root) == 200
    b.release(rb)
    assert a.reserved_bytes(root) == 0


# ------------------------------------------------------------ journal mechanics
def test_journal_compacts_in_place(tmp_path):
    root = str(tmp_path / "r")
    os.makedirs(root)
    led = SharedCapacityLedger(reconcile_interval_s=1e9, compact_min_records=8)
    for i in range(200):
        led.note_written(root, f"f{i % 4}.bin", 10 + i)
    journal = os.path.join(root, LEDGER_DIRNAME, "journal")
    # 200 appends with 4 live files must have been folded away repeatedly
    assert os.path.getsize(journal) < 2048
    with open(journal) as f:
        header = f.readline().split()
    assert header[0] == "SEALEDGER1" and int(header[1]) > 1
    got, want = led.verify(root)
    assert got == sum(10 + i for i in range(196, 200))
    assert want == 0  # nothing physically on disk: pure bookkeeping ops


def test_journal_torn_record_repaired(tmp_path):
    root = str(tmp_path / "r")
    os.makedirs(root)
    # hint_window_s=0: every used_bytes must re-sync (and so repair) the
    # journal instead of serving the <50ms-old replica
    led = SharedCapacityLedger(reconcile_interval_s=1e9, hint_window_s=0.0)
    led.used_bytes(root)  # initial reconcile of the (empty) root
    led.note_written(root, "a.bin", 100)
    journal = os.path.join(root, LEDGER_DIRNAME, "journal")
    with open(journal, "ab") as f:
        f.write(b"W 999999 torn-no-newline")  # writer died mid-append
    assert led.used_bytes(root) == 100  # torn record ignored...
    with open(journal, "rb") as f:
        assert f.read().endswith(b"W 100 a.bin\n")  # ...and truncated away
    led.note_written(root, "b.bin", 50)
    assert led.used_bytes(root) == 150


def test_keys_with_spaces_and_unicode_survive_the_journal(tmp_path):
    root = str(tmp_path / "r")
    os.makedirs(root)
    led = SharedCapacityLedger(reconcile_interval_s=1e9)
    weird = "dir with space/résultat #1.bin"
    led.note_written(root, weird, 321)
    assert led.file_size(root, weird) == 321
    led.note_removed(root, weird)
    assert led.used_bytes(root) == 0


def test_wipe_resets_shared_store(tmp_path):
    cfg = make_config(str(tmp_path))
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "x.bin"), b"x" * 256)
    tier0 = fs.hierarchy.tiers[0]
    fs.wipe()
    assert tier0.used_bytes(tier0.roots[0]) == 0
    fs.write_bytes(os.path.join(fs.mount, "y.bin"), b"y" * 128)
    assert tier0.used_bytes(tier0.roots[0]) == 128


def test_scans_exclude_ledger_store(tmp_path):
    """The per-root store must be invisible to capacity scans, listdir and
    the flusher (it is bookkeeping, not cached application data)."""
    cfg = make_config(str(tmp_path), flushlist=("*",))
    sea = Sea(cfg).start()
    sea.fs.write_bytes(os.path.join(sea.fs.mount, "real.bin"), b"r" * 64)
    try:
        sea.flusher.drain()  # settle the in-flight copy (.sea_tmp) first
        tier0 = sea.fs.hierarchy.tiers[0]
        assert sum(scan_root(tier0.roots[0]).values()) == 64
        got, want = sea.fs.hierarchy.ledger.verify(tier0.roots[0])
        assert got == want == 64
        assert sea.fs.listdir(sea.fs.mount) == ["real.bin"]
        assert sea.flusher.scan() == 1  # only the real file, not the journal
    finally:
        sea.shutdown()


# ----------------------------------------------------------- flusher election
def test_second_instance_in_same_process_is_follower(tmp_path):
    cfg = make_config(str(tmp_path), flushlist=("*.out",), evictlist=("*.out",))
    sea1 = Sea(cfg).start()
    sea2 = Sea(cfg).start()
    try:
        assert sea1.flusher.is_leader and not sea2.flusher.is_leader
        # the follower's close events travel through the spool to the leader
        p = os.path.join(sea2.fs.mount, "routed.out")
        sea2.fs.write_bytes(p, b"s" * 96)
        deadline = time.time() + 15
        base = cfg.tiers[-1].roots[0]
        while not os.path.exists(os.path.join(base, "routed.out")):
            assert time.time() < deadline, "leader never drained the spool"
            time.sleep(0.05)
        # the base copy appears at the flush's os.replace commit, a few
        # ledger transactions BEFORE the MOVE-mode evict of the cache
        # copy runs (flush must durably commit first) — poll for the
        # eviction rather than assuming the two are atomically visible
        while sea2.fs.where(p) != "pfs":
            assert time.time() < deadline, "cache copy never evicted"
            time.sleep(0.05)
    finally:
        sea2.shutdown()
        sea1.shutdown()


def test_leadership_passes_to_next_starter_after_shutdown(tmp_path):
    cfg = make_config(str(tmp_path))
    sea1 = Sea(cfg).start()
    assert sea1.flusher.is_leader
    sea1.shutdown()
    sea2 = Sea(cfg).start()
    try:
        assert sea2.flusher.is_leader
    finally:
        sea2.shutdown()


def test_stop_releases_leadership_even_on_exception(tmp_path):
    cfg = make_config(str(tmp_path))
    sea1 = Sea(cfg).start()
    assert sea1.flusher.is_leader

    def boom(_item):
        raise RuntimeError("queue wedged")

    sea1.flusher._q.put = boom  # make stop() blow up mid-teardown
    with pytest.raises(RuntimeError):
        sea1.flusher.stop()
    # the lockfile was still released: a newcomer can lead immediately
    sea2 = Sea(cfg).start()
    try:
        assert sea2.flusher.is_leader
    finally:
        sea2.shutdown()


def test_sea_start_is_idempotent(tmp_path):
    wd = str(tmp_path)
    base = os.path.join(wd, "pfs")
    os.makedirs(base)
    with open(os.path.join(base, "stage.in"), "wb") as f:
        f.write(b"i" * 128)
    cfg = make_config(wd, prefetchlist=("*.in",))
    sea = Sea(cfg)
    sea.start()
    n_threads = len(sea.flusher._threads)
    prefetched = sea.fs.telemetry.prefetched_bytes
    assert prefetched == 128
    sea.start()  # second start: no new threads, no duplicate prefetch
    assert len(sea.flusher._threads) == n_threads
    assert sea.fs.telemetry.prefetched_bytes == prefetched
    sea.shutdown()
    sea.start()  # restart after shutdown is allowed
    assert sea.flusher._alive()
    sea.shutdown()


# ------------------------------------------------------------------ telemetry
def test_telemetry_aggregate_sums_processes(tmp_path):
    t1, t2 = Telemetry(), Telemetry()
    t1.record_io("tmpfs", written=100)
    t1.record_flush(100)
    t2.record_io("tmpfs", written=50)
    t2.record_io("pfs", read=30)
    agg = aggregate_snapshots([t1.snapshot(), t2.snapshot()])
    assert agg["tiers"]["tmpfs"]["bytes_written"] == 150
    assert agg["tiers"]["pfs"]["bytes_read"] == 30
    assert agg["flushed_bytes"] == 100
    d = str(tmp_path / "stats")
    t1.export(os.path.join(d, "1.json"))
    t2.export(os.path.join(d, "2.json"))
    agg2 = load_aggregate(d)
    assert agg2["tiers"]["tmpfs"]["bytes_written"] == 150
    assert agg2["pids"] == [os.getpid(), os.getpid()]


def test_sea_shutdown_exports_telemetry_in_shared_mode(tmp_path):
    cfg = make_config(str(tmp_path))
    sea = Sea(cfg).start()
    sea.fs.write_bytes(os.path.join(sea.fs.mount, "t.bin"), b"t" * 64)
    sea.shutdown()
    stats_dir = os.path.join(cfg.tiers[-1].roots[0], LEDGER_DIRNAME, "telemetry")
    agg = load_aggregate(stats_dir)
    assert agg["pids"] == [os.getpid()]
    assert agg["tiers"]["tmpfs"]["bytes_written"] == 64


# ---------------------------------------------------------------- configuration
def test_config_parses_shared_ledger_flags(tmp_path):
    ini = tmp_path / "sea.cfg"
    ini.write_text(
        "[sea]\n"
        f"mount = {tmp_path}/mount\n"
        "shared_ledger = true\n"
        "leader_heartbeat_s = 0.25\n"
        f"[tier.fast]\nroots = {tmp_path}/fast\n"
        f"[tier.base]\nroots = {tmp_path}/base\npersistent = true\n"
    )
    cfg = SeaConfig.from_file(str(ini))
    assert cfg.shared_ledger is True
    assert cfg.leader_heartbeat_s == 0.25
    assert isinstance(SeaFS(cfg).hierarchy.ledger, SharedCapacityLedger)


def test_config_rejects_bad_shared_settings(tmp_path):
    with pytest.raises(ValueError):
        make_config(str(tmp_path), leader_heartbeat_s=0.0)


# ------------------------------------------------------------------- simulator
def test_simulator_models_shared_ledger_contention():
    from repro.core.model import ClusterSpec, MiB, Workload
    from repro.core.simulator import Simulator

    cl = ClusterSpec(c=1, p=8)
    w = Workload(B=8, F=64 * MiB, n=6)
    sim_shared = Simulator(cl, w, "sea", shared_ledger=True, ledger_lock_s=1e-3)
    assert sim_shared.flushers_per_node == 1  # leader election: one daemon
    sim_local = Simulator(cl, w, "sea")
    assert sim_local.flushers_per_node == cl.p
    m_shared = sim_shared.run().makespan
    m_free = Simulator(cl, w, "sea", shared_ledger=True, ledger_lock_s=0.0)
    m_free = m_free.run().makespan
    assert m_shared > m_free  # lock queueing costs wall time...
    slow = Simulator(cl, w, "sea", shared_ledger=True, ledger_lock_s=1e-2)
    assert slow.run().makespan > m_shared  # ...and scales with lock length
