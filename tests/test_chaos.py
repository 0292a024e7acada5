"""Chaos suite for the failure-domain layer (ISSUE 10).

Covers the unified errno classification, the fault-injection plane's spec
grammar and seeded determinism, the per-root circuit breaker state machine,
and the end-to-end degradation contracts: a cache root killed mid-workload
(EIO and hung-I/O variants) must leave every read byte-exact, keep opens
succeeding via other roots/peers/base, release reservations on deadline
aborts, and re-admit the root through a half-open probe after recovery.

Seeded reruns: set SEA_CHAOS_SEED to reproduce a CI leg (conftest-free —
each test derives its schedule from the printed seed).
"""

import errno
import io
import os
import random
import threading
import time

import pytest

from repro.core import Sea, SeaConfig, TierSpec
from repro.core import faults
from repro.core.faults import CAPACITY, PERMANENT, TRANSIENT, FaultPlane, classify
from repro.core.health import CLOSED, HALF_OPEN, OPEN, HealthTracker
from repro.core.ledger import scan_root
from repro.core.transfer import TransferDeadlineError

#: randomized-but-printed seed: CI exports SEA_CHAOS_SEED so a failing leg
#: reruns bit-identically (`SEA_CHAOS_SEED=<printed> pytest tests/test_chaos.py`)
CHAOS_SEED = int(os.environ.get("SEA_CHAOS_SEED", "0") or "0") or random.SystemRandom().randrange(1 << 30)
print(f"sea-chaos: SEA_CHAOS_SEED={CHAOS_SEED}")


@pytest.fixture(autouse=True)
def _no_leaked_plane():
    """The fault plane is process-global: never leak one across tests."""
    faults.deactivate()
    yield
    faults.deactivate()


def make_sea(tmp_path, *, roots=("c0",), capacity=None, **kw):
    cfg = SeaConfig(
        mount=str(tmp_path / "mount"),
        tiers=[
            TierSpec(
                name="cache",
                roots=tuple(str(tmp_path / r) for r in roots),
                capacity=capacity,
            ),
            TierSpec(name="pfs", roots=(str(tmp_path / "pfs"),), persistent=True),
        ],
        max_file_size=1 << 16,
        n_procs=1,
        # fast breaker so chaos tests settle in milliseconds, not 30s
        health_window_s=5.0,
        health_min_events=4,
        health_error_threshold=0.5,
        health_open_s=0.2,
        **kw,
    )
    return Sea(cfg)


# ------------------------------------------------------------ classification
def test_classify_table():
    assert classify(OSError(errno.ENOSPC, "")) is CAPACITY
    assert classify(OSError(errno.EDQUOT, "")) is CAPACITY
    assert classify(OSError(errno.EACCES, "")) is PERMANENT
    assert classify(OSError(errno.EISDIR, "")) is PERMANENT
    assert classify(OSError(errno.EIO, "")) is TRANSIENT
    assert classify(ValueError("no errno")) is TRANSIENT
    assert classify(IOError("errno-less IOError")) is TRANSIENT


# ------------------------------------------------------------ fault plane
def test_fault_spec_parsing():
    p = FaultPlane.from_spec(
        "transfer.chunk:errno=EIO,p=0.5,n=3;"
        "seafs.open:delay=0.01,path=*/c0/*;"
        "flusher.flush:torn;"
        "shared_ledger.append:errno=5,after=2"
    )
    actions = [(r.site, r.action) for r in p.rules]
    assert actions == [
        ("transfer.chunk", "errno"),
        ("seafs.open", "delay"),
        ("flusher.flush", "torn"),
        ("shared_ledger.append", "errno"),
    ]
    assert p.rules[0].errno == errno.EIO and p.rules[0].limit == 3
    assert p.rules[1].path_glob == "*/c0/*"
    assert p.rules[3].errno == 5 and p.rules[3].after == 2
    with pytest.raises(ValueError):
        FaultPlane.from_spec("transfer.chunk")  # no action
    with pytest.raises(ValueError):
        FaultPlane.from_spec("transfer.chunk:bogus=1")


def test_fault_schedule_is_seed_deterministic():
    def schedule(seed):
        p = FaultPlane.from_spec("site:errno=EIO,p=0.5", seed=seed)
        out = []
        for _ in range(64):
            try:
                p.fire("site")
                out.append(0)
            except OSError:
                out.append(1)
        return out

    a, b = schedule(CHAOS_SEED), schedule(CHAOS_SEED)
    assert a == b, "same seed must replay the same schedule"
    assert 0 < sum(a) < 64, "p=0.5 should fire sometimes, not always"
    assert schedule(CHAOS_SEED + 1) != a or schedule(CHAOS_SEED + 2) != a


def test_fault_limit_after_and_path_filter(tmp_path):
    p = FaultPlane.from_spec("s:errno=EIO,n=2,after=1,path=*/bad/*")
    faults.activate(p)
    # path filter: non-matching paths never fire
    for _ in range(8):
        faults.fire("s", path="/ok/x")
    # hit 1 skipped (after=1), hits 2-3 fire (n=2), then disarmed
    fired = 0
    for _ in range(8):
        try:
            faults.fire("s", path="/bad/x")
        except OSError:
            fired += 1
    assert fired == 2


def test_fault_delay_is_cancel_aware():
    faults.activate(FaultPlane.from_spec("s:delay=30"))
    cancel = threading.Event()
    t0 = time.monotonic()
    threading.Timer(0.05, cancel.set).start()
    faults.fire("s", cancel=cancel)
    assert time.monotonic() - t0 < 5, "cancel event must unblock the hang"


# ------------------------------------------------------------ breaker unit
def test_breaker_state_machine():
    ht = HealthTracker(min_events=4, error_threshold=0.5, open_s=0.1)
    r = "/r0"
    # below min_events: failures alone never open
    ht.record_failure(r, OSError(errno.EIO, ""))
    ht.record_failure(r, OSError(errno.EIO, ""))
    assert ht.breaker_state(r) == CLOSED and ht.allow(r)
    # 4th event at 75% error rate opens
    ht.record_success(r)
    ht.record_failure(r, OSError(errno.EIO, ""))
    assert ht.breaker_state(r) == OPEN
    assert not ht.allow(r) and ht.quarantined(r)
    # open_s elapsed: exactly one probe admitted (half-open)
    time.sleep(0.12)
    assert ht.allow(r)
    assert ht.breaker_state(r) == HALF_OPEN
    assert not ht.allow(r), "only one outstanding probe"
    # probe success closes and clears the window
    ht.record_success(r)
    assert ht.breaker_state(r) == CLOSED and ht.allow(r)
    snap = ht.snapshot()[r]
    assert snap["state"] == CLOSED and snap["events"] <= 1


def test_breaker_capacity_trips_instantly_and_halfopen_failure_reopens():
    ht = HealthTracker(min_events=100, open_s=0.05)
    r = "/r0"
    ht.record_failure(r, OSError(errno.ENOSPC, ""))  # one event, way below min
    assert ht.breaker_state(r) == OPEN
    time.sleep(0.07)
    assert ht.allow(r)  # half-open probe
    ht.record_failure(r, OSError(errno.EIO, ""))  # probe failed
    assert ht.breaker_state(r) == OPEN
    assert not ht.allow(r)


def test_breaker_stale_probe_claim_expires():
    ht = HealthTracker(open_s=0.05)
    r = "/r0"
    ht.trip(r)
    time.sleep(0.07)
    assert ht.allow(r)  # probe claimed... and the prober dies silently
    assert not ht.allow(r)
    time.sleep(0.07)
    assert ht.allow(r), "a crashed prober must not wedge re-admission"


def test_breaker_telemetry_counters():
    from repro.core.telemetry import Telemetry

    t = Telemetry()
    ht = HealthTracker(open_s=0.02, telemetry=t)
    ht.trip("/r0")
    assert t.breaker_opens == 1 and t.root_quarantines == 1
    time.sleep(0.03)
    assert ht.allow("/r0")
    ht.record_failure("/r0", OSError(errno.EIO, ""))  # half-open re-open
    assert t.breaker_opens == 2
    assert t.root_quarantines == 1, "re-opening is not a NEW quarantine"


# ------------------------------------------------------------ e2e: EIO root
def test_eio_killed_root_degrades_and_readmits(tmp_path):
    sea = make_sea(tmp_path)
    fs = sea.fs
    c0 = str(tmp_path / "c0")
    try:
        payloads = {}
        for i in range(6):
            p = os.path.join(fs.mount, f"f{i}.bin")
            payloads[p] = bytes([i]) * (512 + i)
            with fs.open(p, "wb") as f:
                f.write(payloads[p])
            fs.persist(p)  # a base replica exists: degradation has a target
        # reads must route through the (about to die) cache replica, not
        # the location persist just noted
        fs.resolver.invalidate_all()
        # kill c0: every open of a real under it raises EIO
        faults.activate(FaultPlane.from_spec(f"seafs.open:errno=EIO,path={c0}/*"))
        for p, want in payloads.items():
            with fs.open(p, "rb") as f:
                assert f.read() == want, "degraded read must stay byte-exact"
            fs.resolver.invalidate(fs.key_of(p))  # next read re-hits c0 too
        snap = fs.telemetry.snapshot()
        assert snap["degraded_reads"] >= 6
        # the failure feed opened the breaker: new writes avoid the dead root
        assert fs.health.breaker_state(c0) == OPEN
        for i in range(4):
            p = os.path.join(fs.mount, f"g{i}.bin")
            with fs.open(p, "wb") as f:
                f.write(b"z" * 64)
            with fs.open(p, "rb") as f:
                assert f.read() == b"z" * 64
            assert not os.path.exists(os.path.join(c0, f"g{i}.bin"))
        # recovery: lift the fault, wait out open_s — a half-open probe
        # write re-admits the root
        faults.deactivate()
        deadline = time.time() + 10
        while fs.health.breaker_state(c0) != CLOSED and time.time() < deadline:
            time.sleep(fs.config.health_open_s / 2)
            q = os.path.join(fs.mount, f"probe{time.monotonic_ns()}.bin")
            with fs.open(q, "wb") as f:
                f.write(b"p" * 32)
        assert fs.health.breaker_state(c0) == CLOSED, "root must re-admit"
        # and new writes land on the recovered root again
        p = os.path.join(fs.mount, "recovered.bin")
        with fs.open(p, "wb") as f:
            f.write(b"r" * 64)
        assert os.path.exists(os.path.join(c0, "recovered.bin"))
    finally:
        sea.shutdown()


# ------------------------------------------------------------ e2e: hung I/O
def test_hung_write_aborts_within_deadline_and_releases_reservation(tmp_path):
    # a capped root: free_bytes reads the ledger, not statvfs of a disk
    # other processes write to while this test runs
    sea = make_sea(tmp_path, capacity=1 << 20, transfer_deadline_s=0.25)
    fs = sea.fs
    tier = fs.hierarchy.cache_tiers[0]
    root = tier.roots[0]
    src = str(tmp_path / "pfs" / "hung.bin")
    with open(src, "wb") as f:
        f.write(b"h" * 4096)
    free_before = tier.free_bytes(root)
    faults.activate(FaultPlane.from_spec("transfer.chunk:delay=60,n=1"))
    t0 = time.monotonic()
    try:
        with pytest.raises(TransferDeadlineError) as ei:
            fs.transfer.copy(
                src,
                os.path.join(root, "hung.bin"),
                src_tier=fs.hierarchy.base,
                dst_tier=tier,
                dst_root=root,
                key="hung.bin",
                admit="require",
            )
        elapsed = time.monotonic() - t0
        assert elapsed < 10, f"hung copy must abort cooperatively ({elapsed:.1f}s)"
        assert ei.value.errno == errno.ETIMEDOUT
        snap = fs.telemetry.snapshot()
        assert snap["deadline_aborts"] == 1
        assert fs.health.breaker_state(root) == OPEN, "deadline trips the breaker"
        assert tier.reserved_bytes(root) == 0, "aborted copy must release its budget"
        assert tier.free_bytes(root) == free_before
        assert not os.path.exists(os.path.join(root, "hung.bin"))
        residue = [n for n in os.listdir(root) if ".sea_tmp" in n]
        assert residue == [], residue
    finally:
        sea.shutdown()


# ------------------------------------------------------ e2e: ENOSPC per root
@pytest.mark.parametrize("bad", ["c0", "c1"])
def test_enospc_mid_write_degrades_on_each_root(tmp_path, bad):
    sea = make_sea(tmp_path, roots=("c0", "c1"))
    fs = sea.fs
    badroot = str(tmp_path / bad)
    want = b"A" * 300 + b"B" * 300 + b"C" * 300
    try:
        faults.activate(
            FaultPlane.from_spec(f"seafs.write:errno=ENOSPC,path={badroot}/*")
        )
        # keep writing until a write actually started on the bad root
        # (placement shuffles roots), then every later write avoids it
        for i in range(12):
            p = os.path.join(fs.mount, f"e{i}.bin")
            with fs.open(p, "wb") as f:
                f.write(want[:300])
                f.write(want[300:600])
                f.write(want[600:])
            with fs.open(p, "rb") as f:
                assert f.read() == want, f"{p} must stay byte-exact"
        assert fs.health.breaker_state(badroot) == OPEN
        faults.deactivate()
        # ledger matches a walk on every root (no phantom/missing bytes)
        for tier in fs.hierarchy.tiers:
            for r in tier.roots:
                walked = sum(scan_root(r).values())
                assert tier.used_bytes(r) == walked, (r, tier.used_bytes(r), walked)
                assert tier.reserved_bytes(r) == 0
        # no torn staging residue anywhere
        for r, _, names in os.walk(tmp_path):
            for n in names:
                assert not n.endswith((".sea_part", ".sea_tmp")), os.path.join(r, n)
    finally:
        sea.shutdown()


# ------------------------------------------- relocation: partial raw write
class _PartialFullRaw(io.RawIOBase):
    """Raw writer that lands a prefix of a large write on disk and then
    raises ENOSPC — what a filling device does to a BufferedWriter whose
    big write bypasses the buffer. Post-failure tell() counts the landed
    prefix, so relocation trusting it would duplicate those bytes."""

    def __init__(self, path, fire_at, partial):
        super().__init__()
        self._f = open(path, "wb", buffering=0)
        self._fire_at = fire_at
        self._partial = partial
        self.fired = False

    def writable(self):
        return True

    def seekable(self):
        return True

    def write(self, b):
        b = bytes(b)
        if not self.fired and len(b) >= self._fire_at:
            self.fired = True
            self._f.write(b[: self._partial])
            raise OSError(errno.ENOSPC, "device full (injected, partial)")
        return self._f.write(b)

    def seek(self, pos, whence=0):
        return self._f.seek(pos, whence)

    def tell(self):
        return self._f.tell()

    def fileno(self):
        return self._f.fileno()

    def close(self):
        if not self.closed:
            self._f.close()
        super().close()


def test_enospc_partial_direct_write_relocates_without_duplication(tmp_path):
    """REVIEW regression: ENOSPC striking after k bytes of a big buffered
    write already reached the raw fd must not duplicate those k bytes
    when the handle migrates — the relocated file rewinds to the
    pre-write position, not to post-failure tell()."""
    sea = make_sea(tmp_path, roots=("c0", "c1"))
    fs = sea.fs
    prefix, big = b"x" * 10, b"D" * 100
    try:
        p = os.path.join(fs.mount, "dup.bin")
        f = fs.open(p, "wb")
        old_real = f._real
        f._raw.close()  # swap in a raw layer that fails like a full disk
        f._raw = io.BufferedWriter(
            _PartialFullRaw(old_real, fire_at=64, partial=7), buffer_size=16
        )
        assert f.write(prefix) == len(prefix)  # sits in the buffer
        # big write: buffer flushes (10B), then 7B of `big` land on the
        # raw fd before ENOSPC -> handle must relocate and keep going
        assert f.write(big) == len(big)
        assert f._real != old_real, "handle must have migrated"
        f.close()
        with fs.open(p, "rb") as g:
            got = g.read()
        assert got == prefix + big, (
            f"relocated write duplicated the partially-landed prefix: "
            f"len={len(got)}, want={len(prefix + big)}"
        )
    finally:
        sea.shutdown()


# ---------------------------------------------- enumeration vs. probe claim
def test_admissible_is_pure_and_allow_still_claims():
    ht = HealthTracker(open_s=0.05)
    r = "/r0"
    ht.trip(r)
    assert not ht.admissible(r)
    time.sleep(0.07)
    for _ in range(10):
        assert ht.admissible(r), "enumeration must be repeatable (no claim)"
    assert ht.breaker_state(r) == OPEN, "pure queries must not transition"
    assert ht.allow(r), "the actual claim still gets the probe slot"
    assert ht.breaker_state(r) == HALF_OPEN
    assert not ht.admissible(r), "a fresh outstanding probe filters the root"
    time.sleep(0.07)
    assert ht.admissible(r), "a stale probe claim re-opens enumeration"


def test_enumeration_does_not_starve_halfopen_readmission(tmp_path):
    """REVIEW regression: placement/spill eligibility queries used to call
    allow(), consuming the single half-open probe slot without doing any
    I/O — starving a recovered root's re-admission indefinitely."""
    sea = make_sea(tmp_path)
    fs = sea.fs
    tier = fs.hierarchy.cache_tiers[0]
    root = tier.roots[0]
    try:
        fs.health.trip(root)
        time.sleep(fs.config.health_open_s + 0.05)
        for _ in range(25):  # placement queries / spill checks
            fs.policy.eligible_roots(tier)
        assert fs.health.breaker_state(root) == OPEN, (
            "eligibility enumeration must not consume the probe slot"
        )
        p = os.path.join(fs.mount, "probe.bin")
        with fs.open(p, "wb") as f:
            f.write(b"p" * 32)
        assert fs.health.breaker_state(root) == CLOSED, (
            "the first real write claims the probe and re-admits the root"
        )
    finally:
        sea.shutdown()


# --------------------------------------------------- watchdog thread hygiene
def test_idle_watchdog_thread_exits_and_respawns(tmp_path):
    """REVIEW regression: the deadline watchdog used to spin for the life
    of the process once armed — it must exit when nothing is in flight
    and respawn lazily for the next armed copy."""
    sea = make_sea(tmp_path, transfer_deadline_s=0.2)
    fs = sea.fs
    try:
        src = str(tmp_path / "pfs" / "w.bin")
        with open(src, "wb") as f:
            f.write(b"w" * 1024)
        for i in range(2):
            fs.transfer.copy(src, str(tmp_path / "pfs" / f"w{i}.out"))
            deadline = time.monotonic() + 5
            while (
                fs.transfer._watch_thread is not None
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert fs.transfer._watch_thread is None, (
                "watchdog must exit once no copies are in flight"
            )
    finally:
        sea.shutdown()


# ------------------------------------------------ extent stalls feed breaker
def test_range_deadline_abort_trips_destination_breaker(tmp_path):
    """REVIEW regression: a deadline abort on an extent/range copy used to
    pass root=None, so extent stalls never quarantined the destination
    root the way whole-file stalls do."""
    sea = make_sea(tmp_path, transfer_deadline_s=0.25, transfer_chunk_bytes=2048)
    fs = sea.fs
    tier = fs.hierarchy.cache_tiers[0]
    root = tier.roots[0]
    src = str(tmp_path / "pfs" / "ext.bin")
    with open(src, "wb") as f:
        f.write(b"e" * 8192)
    dst = os.path.join(root, "ext.partfile")
    with open(dst, "wb") as f:
        f.truncate(8192)
    faults.activate(FaultPlane.from_spec("transfer.range_chunk:delay=60,n=1"))
    try:
        with pytest.raises(TransferDeadlineError):
            fs.transfer.copy_range(
                src,
                dst,
                0,
                8192,
                src_tier=fs.hierarchy.base,
                dst_tier=tier,
                dst_root=root,
            )
        assert fs.health.breaker_state(root) == OPEN, (
            "an extent-stage stall must trip the destination root's breaker"
        )
        assert fs.telemetry.snapshot()["deadline_aborts"] >= 1
    finally:
        sea.shutdown()


# ------------------------------------------------------------ flusher fixes
def test_flusher_retries_all_eligible_failed_keys(tmp_path):
    sea = make_sea(tmp_path)
    fl = sea.flusher
    try:
        resubmitted = []
        fl.submit = lambda key: resubmitted.append(key)  # record, don't flush
        now = time.monotonic()
        with fl._cv:
            fl._failed.update(
                {"a": now - 1, "b": now - 1, "c": now - 1, "later": now + 60}
            )
        fl._maybe_retry_failed()
        assert sorted(resubmitted) == ["a", "b", "c"], (
            "a recovered tier must drain the whole backlog in one tick"
        )
        with fl._cv:
            assert set(fl._failed) == {"later"}, "unexpired backoffs stay parked"
    finally:
        sea.shutdown()


class _HungThread:
    name = "sea-fake-hung"

    def join(self, timeout=None):
        pass  # "times out" instantly

    def is_alive(self):
        return True

    def start(self):
        pass


def test_hung_thread_joins_counted_on_stop(tmp_path, capsys):
    sea = make_sea(tmp_path)
    fs = sea.fs
    try:
        fl = sea.flusher
        fl.stop()  # settle the real workers first
        fl._threads = [_HungThread()]
        fl._q.put(None)
        fl.stop()
        assert fs.telemetry.hung_thread_joins == 1
        fs.prefetcher._thread = _HungThread()
        fs.prefetcher.stop()
        assert fs.telemetry.hung_thread_joins == 2
        err = capsys.readouterr().err
        assert "still alive" in err
    finally:
        sea.shutdown()


# ------------------------------------------------------------ config plumbing
def test_config_activates_fault_plane(tmp_path):
    sea = make_sea(
        tmp_path, faults="transfer.chunk:errno=EIO,p=0.0", fault_seed=CHAOS_SEED
    )
    try:
        plane = faults.active_plane()
        assert plane is not None and plane.seed == CHAOS_SEED
        assert [r.site for r in plane.rules] == ["transfer.chunk"]
    finally:
        sea.shutdown()
