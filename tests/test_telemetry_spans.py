"""Telemetry spans: off by default at no clock, exact per-thread totals
when on, carried through snapshot/export/aggregate, recorded where the
work happens (SeaFS open/read/write/close and admission, the flusher,
the device feed), and on the profiler's clock where jax is imported."""

import glob
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.core import Sea, SeaConfig, SeaMount, TierSpec
from repro.core.telemetry import SPANS, Telemetry, aggregate_snapshots, load_aggregate
from repro.data.pipeline import DataPipeline, write_dataset

KiB = 1 << 10
MiB = 1 << 20
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_sea(tmp_path, **kw) -> Sea:
    cfg = SeaConfig(
        mount=str(tmp_path / "mount"),
        tiers=[
            TierSpec(name="tmpfs", roots=(str(tmp_path / "t0"),)),
            TierSpec(name="pfs", roots=(str(tmp_path / "pfs"),), persistent=True),
        ],
        max_file_size=16 * MiB,
        n_procs=1,
        **kw,
    )
    return Sea(cfg)


def spans(tel: Telemetry) -> dict:
    return tel.snapshot()["spans"]


def recorded(tel: Telemetry) -> dict:
    return {k: v for k, v in spans(tel).items() if v["count"]}


def exercise(sea: Sea) -> None:
    """Every span site once or more: a write (open, admission, write,
    close), a read, a MOVE flush of the final, the device feed."""
    fs = sea.fs
    with fs.open(os.path.join(fs.mount, "a_final.bin"), "wb") as f:
        f.write(b"x" * 4096)
    with fs.open(os.path.join(fs.mount, "a_final.bin"), "rb") as f:
        assert f.read() == b"x" * 4096
    sea.flusher._process_all_sync()
    write_dataset(sea, "c", n_shards=2, tokens_per_shard=1024, vocab_size=50)

    def slow_put(batch):
        time.sleep(0.01)  # the consumer finds the feed empty at least once
        return batch

    with DataPipeline(sea, "c", batch_size=2, seq_len=32) as pipe:
        assert list(pipe.device_iter(depth=1, put_fn=slow_put))


# ------------------------------------------------------------------ spans off
def test_spans_off_record_nothing_and_read_no_clock(tmp_path, monkeypatch):
    sea = make_sea(tmp_path, flushlist=("*_final.bin",), evictlist=("*_final.bin",))
    tel = sea.fs.telemetry
    assert tel.spans_on is False

    def no_clock():
        raise AssertionError("a span site read a clock with spans off")

    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    monkeypatch.setattr(time, "thread_time_ns", no_clock)
    try:
        exercise(sea)
        monkeypatch.undo()
        snap = spans(tel)
        assert set(snap) == set(SPANS)
        assert all(v == {"count": 0, "wall_s": 0.0, "cpu_s": 0.0, "bytes": 0}
                   for v in snap.values())
        assert os.path.exists(tmp_path / "pfs" / "a_final.bin")
        assert not os.path.exists(tmp_path / "t0" / "a_final.bin")
        assert tel.snapshot()["device_feed_stalls"] >= 1
    finally:
        sea.shutdown()


def test_repro_core_never_imports_jax(tmp_path):
    code = textwrap.dedent(f"""
        import os, sys
        from repro.core import Sea, SeaConfig, TierSpec
        cfg = SeaConfig(mount={str(tmp_path / "m")!r}, tiers=[
            TierSpec(name="tmpfs", roots=({str(tmp_path / "t")!r},)),
            TierSpec(name="pfs", roots=({str(tmp_path / "p")!r},), persistent=True)])
        sea = Sea(cfg)
        for on in (False, True):
            sea.fs.telemetry.trace_spans(on)
            with sea.fs.open(os.path.join(sea.fs.mount, "k"), "wb") as f:
                f.write(b"k")
            with sea.fs.open(os.path.join(sea.fs.mount, "k"), "rb") as f:
                f.read()
        assert sea.fs.telemetry.snapshot()["spans"]["sea.read"]["count"] == 1
        sea.shutdown()
        assert "jax" not in sys.modules, "repro.core imported jax"
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# ------------------------------------------------------------------ spans on
def test_span_totals_exact_across_threads():
    """Four writer threads and a reader snapshotting while they run, with
    a short switch interval: once the writers join, every span counts."""
    tel = Telemetry()
    tel.trace_spans(True)
    n, barrier = 500, threading.Barrier(5)

    def work(i):
        barrier.wait()
        for _ in range(n):
            with tel.span("sea.read", nbytes=i + 1):
                pass
            with tel.span("sea.write") as sp:
                sp.nbytes = 10
            tel.record_span("flush.queued", 0.001)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        barrier.wait()
        while any(t.is_alive() for t in threads):
            assert spans(tel)["sea.read"]["count"] <= 4 * n
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    snap = spans(tel)
    assert snap["sea.read"]["count"] == 4 * n
    assert snap["sea.read"]["bytes"] == n * (1 + 2 + 3 + 4)
    assert snap["sea.write"]["count"] == 4 * n and snap["sea.write"]["bytes"] == 40 * n
    assert snap["flush.queued"]["count"] == 4 * n
    assert snap["flush.queued"]["wall_s"] == pytest.approx(4 * n * 0.001)
    assert snap["flush.queued"]["cpu_s"] == 0
    assert snap["sea.read"]["wall_s"] > 0 and snap["sea.read"]["cpu_s"] >= 0
    # the dead threads' blocks were folded in once: a second look agrees
    assert spans(tel) == snap


def test_every_span_site_records(tmp_path):
    sea = make_sea(tmp_path, flushlist=("*_final.bin",), evictlist=("*_final.bin",))
    tel = sea.fs.telemetry
    tel.trace_spans(True)
    try:
        exercise(sea)
        got = recorded(tel)
        assert set(got) == set(SPANS)
        snap = tel.snapshot()
        assert got["flush.move"]["count"] == 1       # the one MOVE of a_final.bin
        assert got["flush.move"]["bytes"] == 4096
        assert got["flush.queued"]["count"] == 1
        assert got["feed.wait"]["count"] == snap["device_feed_stalls"]
        assert got["feed.put"]["wall_s"] >= 0.01 * got["feed.put"]["count"]
        # one admission per fresh write open; the 4096-byte write
        assert got["sea.admit"]["count"] == got["sea.open.write"]["count"]
        assert got["sea.write"]["bytes"] >= 4096
        assert got["sea.close"]["count"] == got["sea.open.write"]["count"]
        assert all(parent is None or parent in SPANS for parent, _ in SPANS.values())
    finally:
        sea.shutdown()


def test_snapshot_export_aggregate_carry_spans(tmp_path):
    t1, t2 = Telemetry(), Telemetry()
    for t in (t1, t2):
        t.trace_spans(True)
        with t.span("sea.read", nbytes=100):
            pass
        t.record_io("tmpfs", written=10)
        t.record_io("tmpfs", read=10)
    snap = t1.snapshot()
    assert snap["spans"]["sea.read"]["count"] == 1
    assert set(snap["tiers"]["tmpfs"]) == {"bytes_written", "bytes_read", "files_written",
                                           "files_read"}
    agg = aggregate_snapshots([t1.snapshot(), t2.snapshot()])
    assert agg["spans"]["sea.read"]["count"] == 2
    assert agg["spans"]["sea.read"]["bytes"] == 200
    d = str(tmp_path / "stats")
    t1.export(os.path.join(d, "1.json"))
    t2.export(os.path.join(d, "2.json"))
    loaded = load_aggregate(d)
    assert loaded["spans"]["sea.read"] == agg["spans"]["sea.read"]
    assert not any(k.endswith("_seconds") for c in loaded["tiers"].values() for k in c)


def test_np_load_reads_in_256_kib_calls(tmp_path):
    """numpy reads a non-``FileIO`` handle in ``format.BUFFER_SIZE`` calls:
    a 4 MiB float32 block more costs 16 more ``sea.read`` calls of 256 KiB."""
    sea = make_sea(tmp_path)
    tel = sea.fs.telemetry
    tel.trace_spans(True)
    per_load = []
    try:
        with SeaMount(sea.fs):
            for mib in (4, 8):
                path = os.path.join(sea.fs.mount, f"b{mib}.npy")
                np.save(path, np.ones(mib * MiB // 4, np.float32))
                before = spans(tel)["sea.read"]
                arr = np.load(path)
                after = spans(tel)["sea.read"]
                assert arr.nbytes == mib * MiB
                per_load.append((after["count"] - before["count"],
                                 after["bytes"] - before["bytes"]))
    finally:
        sea.shutdown()
    (c4, b4), (c8, b8) = per_load
    assert c8 - c4 == 16 and b8 - b4 == 4 * MiB
    assert c4 - 16 == c8 - 32 <= 6            # the header's few small reads
    assert 0 < b4 - 4 * MiB < KiB


def test_spans_are_host_events_on_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    sea = make_sea(tmp_path)
    tel = sea.fs.telemetry
    path = os.path.join(sea.fs.mount, "t.bin")
    try:
        with sea.fs.open(path, "wb") as f:
            f.write(b"t" * 1024)
        tel.trace_spans(True)
        trace_dir = str(tmp_path / "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("outer"):
                with sea.fs.open(path, "rb") as f:
                    assert len(f.read()) == 1024
        finally:
            jax.profiler.stop_trace()
    finally:
        sea.shutdown()
    (pb,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    events = [(ev.name, ev.start_ns, ev.end_ns)
              for plane in ProfileData.from_file(pb).planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    (outer,) = [(s, e) for n, s, e in events if n == "outer"]
    for name in ("sea.open.read", "sea.read"):
        inside = [(s, e) for n, s, e in events if n == name]
        assert inside, f"no {name} event in the trace"
        assert all(outer[0] <= s and e <= outer[1] for s, e in inside)
