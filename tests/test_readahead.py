"""Adaptive read path: predictive readahead + the open fast path.

Covers the predictor models (numeric runs, successor graph, confidence
gate, depth adaptation, cancellation), end-to-end speculative staging
with ledger admission, eviction shielding of predicted-hot keys, the
read-hit open fast path (counters, toggles, writer diversion), and the
concurrent readers/writers/mover stress required by ISSUE 5."""

import os
import random
import threading
import time

import pytest

from repro.core import Sea, SeaConfig, SeaFS, TierSpec
from repro.core.flusher import Flusher
from repro.core.lists import Mode


def make_config(tmp_path, **kw):
    defaults = dict(
        mount=str(tmp_path / "mount"),
        tiers=[
            TierSpec(name="tmpfs", roots=(str(tmp_path / "t0"),)),
            TierSpec(name="pfs", roots=(str(tmp_path / "pfs"),), persistent=True),
        ],
        max_file_size=1 << 16,
        n_procs=2,
        readahead=True,
    )
    defaults.update(kw)
    return SeaConfig(**defaults)


def wait_until(cond, timeout=5.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def seed_base_shards(fs, n, nbytes=4096, prefix="shard"):
    """Write n sequential shards and leave them ONLY on the base tier."""
    for i in range(n):
        p = os.path.join(fs.mount, f"{prefix}_{i:05d}.bin")
        fs.write_bytes(p, bytes([i % 256]) * nbytes)
        fs.persist(p)
    for tier in fs.hierarchy.cache_tiers:
        tier.wipe()
    fs.resolver.invalidate_all()


# ---------------------------------------------------------------- predictor
def test_numeric_run_detection_and_prediction(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    now = time.monotonic()
    assert pf._update_numeric("a/shard_00001.npy", now) == []
    assert pf._update_numeric("a/shard_00002.npy", now) == []  # stride set
    preds = pf._update_numeric("a/shard_00003.npy", now)  # confirmed
    assert [p[0] for p in preds] == ["a/shard_00004.npy"]  # depth starts at 1


def test_strided_sequences_predicted(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    now = time.monotonic()
    for i in (0, 2, 4):
        preds = pf._update_numeric(f"s_{i:04d}.bin", now)
    assert [p[0] for p in preds] == ["s_0006.bin"]


def test_stride_change_resets_confidence(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    now = time.monotonic()
    for i in (1, 2, 3):
        pf._update_numeric(f"k_{i:03d}", now)
    # jump breaks the run: no prediction until the new stride is confirmed
    assert pf._update_numeric("k_042", now) == []
    assert pf._update_numeric("k_050", now) == []  # stride 8, unconfirmed
    assert [p[0] for p in pf._update_numeric("k_058", now)] == ["k_066"]


def test_random_order_yields_no_numeric_predictions(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    now = time.monotonic()
    rng = random.Random(7)
    order = rng.sample(range(500), 60)
    preds = []
    for i in order:
        preds += pf._update_numeric(f"r_{i:04d}", now)
    # equal consecutive deltas in a 60-draw random sample are rare
    assert len(preds) <= 3


def test_successor_graph_predicts_repeated_transitions(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    for _ in range(3):
        pf._update_successor("alpha")
        pf._update_successor("beta")
        pf._update_successor("gamma")
    pf._update_successor("gamma", predict=False)
    assert [p[0] for p in pf._update_successor("alpha")] == ["beta"]


def test_confidence_gate_blocks_short_runs(tmp_path):
    fs = SeaFS(make_config(tmp_path, readahead_min_confidence=0.9))
    pf = fs.prefetcher
    now = time.monotonic()
    preds = []
    for i in range(8):  # run length 7: confidence 1-1/7 ~ 0.857 < 0.9
        preds += pf._update_numeric(f"c_{i:03d}", now)
    assert preds == []
    for i in range(8, 13):  # length 12: 1-1/12 ~ 0.92 >= 0.9
        preds += pf._update_numeric(f"c_{i:03d}", now)
    assert preds


def test_config_validation():
    with pytest.raises(ValueError):
        SeaConfig(
            mount="/tmp/x",
            tiers=[TierSpec(name="b", roots=("/tmp/b",), persistent=True)],
            readahead_depth=0,
        )
    with pytest.raises(ValueError):
        SeaConfig(
            mount="/tmp/x",
            tiers=[TierSpec(name="b", roots=("/tmp/b",), persistent=True)],
            readahead_min_confidence=1.5,
        )


# ------------------------------------------------------- speculative staging
def test_sequential_reads_stage_ahead_and_hit(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    seed_base_shards(fs, 12)
    for i in range(12):
        p = os.path.join(fs.mount, f"shard_{i:05d}.bin")
        with fs.open(p, "rb") as f:
            assert f.read() == bytes([i]) * 4096
        # per-block "compute": the window the predictor stages under
        # (a tight loop would outrun speculation by design)
        time.sleep(0.03)
    fs.prefetcher.stop()
    snap = fs.telemetry.snapshot()
    assert snap["readahead_predictions"] > 0
    assert snap["readahead_staged_files"] >= 3
    assert snap["readahead_hits"] >= 3
    # staged replicas really live on the cache tier and are ledger-visible
    cache = fs.hierarchy.cache_tiers[0]
    got, want = fs.hierarchy.ledger.verify(cache.roots[0])
    assert got == want


def test_depth_widens_with_hits(tmp_path):
    fs = SeaFS(make_config(tmp_path, readahead_depth=4))
    seed_base_shards(fs, 24)
    for i in range(24):
        p = os.path.join(fs.mount, f"shard_{i:05d}.bin")
        with fs.open(p, "rb") as f:
            f.read()
        time.sleep(0.02)
    assert wait_until(
        lambda: any(r.depth > 1 for r in fs.prefetcher._runs.values())
    )
    fs.prefetcher.stop()


def test_random_access_stages_and_wastes_little(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    seed_base_shards(fs, 24)
    rng = random.Random(3)
    order = list(range(24))
    rng.shuffle(order)
    for i in order:
        p = os.path.join(fs.mount, f"shard_{i:05d}.bin")
        with fs.open(p, "rb") as f:
            f.read()
    time.sleep(0.3)  # let in-flight speculation settle
    fs.prefetcher.stop()  # settles pending predictions as waste
    snap = fs.telemetry.snapshot()
    staged = snap["readahead_staged_bytes"]
    wasted = snap["readahead_wasted_bytes"]
    assert wasted <= max(0.2 * staged, 0)


def test_direction_change_cancels_pending(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    # establish an ascending run whose predictions cannot stage (the
    # keys don't exist), so they stay pending
    for i in (1, 2, 3, 4):
        pf._observe_one(f"ghost_{i:04d}")
    assert wait_until(lambda: pf.pending_count() > 0)
    pf._observe_one("ghost_0002")  # direction change: descending
    assert pf.pending_count() == 0
    fs.prefetcher.stop()


def test_stop_settles_pending_as_waste(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    pf = fs.prefetcher
    for i in (1, 2, 3):
        pf._observe_one(f"ghost_{i:04d}")
    assert wait_until(lambda: pf.pending_count() > 0)
    pf.stop()
    assert pf.pending_count() == 0


def test_disabled_prefetcher_is_inert(tmp_path):
    fs = SeaFS(make_config(tmp_path, readahead=False))
    seed_base_shards(fs, 6)
    for i in range(6):
        with fs.open(os.path.join(fs.mount, f"shard_{i:05d}.bin"), "rb") as f:
            f.read()
    assert fs.prefetcher._thread is None
    snap = fs.telemetry.snapshot()
    assert snap["readahead_predictions"] == 0
    assert snap["readahead_staged_bytes"] == 0
    assert not fs.prefetcher.is_hot("shard_00001.bin")


# ------------------------------------------------------- eviction shielding
def test_flusher_defers_evict_of_predicted_hot_keys(tmp_path):
    cfg = make_config(tmp_path, evictlist=("hotkey.bin",))
    fs = SeaFS(cfg)
    flusher = Flusher(fs)
    p = os.path.join(fs.mount, "hotkey.bin")
    fs.write_bytes(p, b"h" * 128)  # REMOVE mode, sits in cache
    fs.prefetcher._recent["hotkey.bin"] = time.monotonic()  # mark hot
    assert flusher.process("hotkey.bin") is Mode.REMOVE
    assert fs.where(p) == "tmpfs"  # evict was deferred, not executed
    fs.prefetcher._recent.clear()  # hotness gone
    flusher.process("hotkey.bin")
    assert fs.where(p) is None  # now evicted


def test_drain_evicts_hot_keys_anyway(tmp_path):
    cfg = make_config(tmp_path, evictlist=("hotkey.bin",))
    with Sea(cfg) as sea:
        fs = sea.fs
        p = os.path.join(fs.mount, "hotkey.bin")
        fs.write_bytes(p, b"h" * 128)
        fs.prefetcher._recent["hotkey.bin"] = time.monotonic()
    # shutdown drained: REMOVE-mode files must be gone despite hotness
    fs2 = SeaFS(make_config(tmp_path))
    assert fs2.where(os.path.join(fs2.mount, "hotkey.bin")) is None


def test_lru_evicts_cold_before_predicted_hot(tmp_path):
    F = 1 << 10
    cfg = make_config(
        tmp_path, lru_evict=True, max_file_size=F, n_procs=1
    )
    cfg.tiers[0].capacity = 2 * F
    fs = SeaFS(cfg)
    fs.write_bytes(os.path.join(fs.mount, "hot.bin"), b"h" * F)
    fs.write_bytes(os.path.join(fs.mount, "cold.bin"), b"c" * F)
    # hot.bin is older (LRU would pick it) but predicted-hot
    fs._access_clock["hot.bin"] = 1.0
    fs._access_clock["cold.bin"] = 2.0
    fs.prefetcher._recent["hot.bin"] = time.monotonic()
    fs.write_bytes(os.path.join(fs.mount, "new.bin"), b"n" * F)
    assert fs.where(os.path.join(fs.mount, "hot.bin")) == "tmpfs"
    assert fs.where(os.path.join(fs.mount, "cold.bin")) is None


# ------------------------------------------------------------ open fast path
def test_fast_path_serves_warm_rereads(tmp_path):
    fs = SeaFS(make_config(tmp_path, readahead=False))
    p = os.path.join(fs.mount, "warm.bin")
    fs.write_bytes(p, b"w" * 256)
    for _ in range(10):
        with fs.open(p, "rb") as f:
            assert f.read() == b"w" * 256
    snap = fs.telemetry.snapshot()
    assert snap["fastpath_opens"] >= 8
    # batched per-thread read counters fold into the per-tier view
    assert snap["tiers"]["tmpfs"]["bytes_read"] >= 8 * 256


def test_fast_path_respects_strict_verify_window(tmp_path):
    fs = SeaFS(make_config(tmp_path, resolver_verify_window_s=0.0,
                           readahead=False))
    p = os.path.join(fs.mount, "warm.bin")
    fs.write_bytes(p, b"w" * 256)
    for _ in range(5):
        with fs.open(p, "rb") as f:
            f.read()
    # window 0 = verify every hit: the lock-free path must never serve
    assert fs.telemetry.snapshot()["fastpath_opens"] == 0


def test_fast_path_diverts_while_writer_open(tmp_path):
    fs = SeaFS(make_config(tmp_path, readahead=False))
    p = os.path.join(fs.mount, "rw.bin")
    fs.write_bytes(p, b"x" * 128)
    with fs.open(p, "rb") as f:  # prime the trust window
        f.read()
    before = fs.telemetry.snapshot()["fastpath_opens"]
    w = fs.open(p, "wb")
    try:
        with fs.open(p, "rb") as f:
            f.read()
        assert fs.telemetry.snapshot()["fastpath_opens"] == before
    finally:
        w.close()


def test_fast_path_relative_and_dotted_paths_still_route(tmp_path, monkeypatch):
    """Unnormalized spellings must fall back to the abspath slow path and
    resolve to the same file — never misroute."""
    fs = SeaFS(make_config(tmp_path, readahead=False))
    p = os.path.join(fs.mount, "norm.bin")
    fs.write_bytes(p, b"n" * 64)
    dotted = os.path.join(fs.mount, ".", "norm.bin")
    with fs.open(dotted, "rb") as f:
        assert f.read() == b"n" * 64
    monkeypatch.chdir(fs.mount)
    with fs.open("norm.bin", "rb") as f:
        assert f.read() == b"n" * 64


def test_fast_path_heals_after_external_move(tmp_path):
    fs = SeaFS(make_config(tmp_path))
    p = os.path.join(fs.mount, "mv.bin")
    fs.write_bytes(p, b"m" * 64)
    with fs.open(p, "rb") as f:
        f.read()
    # external process moves the file cache->base (flusher MOVE analogue)
    key = "mv.bin"
    cached = fs.hierarchy.cache_tiers[0].locate(key)
    base = os.path.join(fs.hierarchy.base.roots[0], key)
    os.makedirs(os.path.dirname(base), exist_ok=True)
    os.replace(cached, base)
    with fs.open(p, "rb") as f:  # fast path ENOENT -> slow path heals
        assert f.read() == b"m" * 64


def test_fast_path_stress_no_partial_no_unknown_content(tmp_path):
    """ISSUE 5 satellite: fast-path hits under concurrent writers and
    flusher MOVE migrations must never observe a half-committed write or
    a mid-flush move — every read returns one complete committed
    generation (the zero-stale-reads discipline of test_resolver)."""
    cfg = make_config(
        tmp_path, flushlist=("hot/*",), evictlist=("hot/*",), readahead=False
    )
    n_keys, gens, size = 6, 25, 1024
    errors: list = []
    with Sea(cfg) as sea:
        fs = sea.fs
        valid = {i: set() for i in range(n_keys)}
        stop = threading.Event()

        def writer(i):
            try:
                for g in range(gens):
                    data = bytes([g % 256]) * (size // 2) + bytes([i]) * (
                        size // 2
                    )
                    tmp = os.path.join(fs.mount, f"hot/t{i}_{g}.bin")
                    dst = os.path.join(fs.mount, f"hot/k{i}.bin")
                    fs.write_bytes(tmp, data)
                    valid[i].add(data)  # registered BEFORE it becomes visible
                    fs.rename(tmp, dst)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    for i in range(n_keys):
                        p = os.path.join(fs.mount, f"hot/k{i}.bin")
                        try:
                            with fs.open(p, "rb") as f:
                                got = f.read()
                        except FileNotFoundError:
                            continue  # mid-move window may miss…
                        if len(got) != size or got not in valid[i]:
                            errors.append(
                                AssertionError(
                                    f"k{i}: read {len(got)} bytes, "
                                    f"known={got in valid[i]}"
                                )
                            )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        writers = [
            threading.Thread(target=writer, args=(i,)) for i in range(n_keys)
        ]
        readers = [threading.Thread(target=reader) for _ in range(3)]
        for t in writers + readers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in readers:
            t.join()
        assert not errors
        assert fs.telemetry.snapshot()["fastpath_opens"] > 0
    # after drain, every key holds its final generation on the base tier
    fs2 = SeaFS(cfg)
    for i in range(n_keys):
        p = os.path.join(fs2.mount, f"hot/k{i}.bin")
        assert fs2.where(p) == "pfs"
        got = fs2.read_bytes(p)
        assert got == bytes([(gens - 1) % 256]) * (size // 2) + bytes(
            [i]
        ) * (size // 2)


def test_data_pipeline_relies_on_predictor(tmp_path):
    """With readahead on, the pipeline's bespoke staging is dropped and
    the predictor drives staging off the sequential shard opens —
    batches must be identical either way."""
    from repro.data.pipeline import DataPipeline, write_dataset

    cfg = make_config(tmp_path, max_file_size=1 << 22)
    with Sea(cfg) as sea:
        write_dataset(sea, "c", n_shards=5, tokens_per_shard=4096,
                      vocab_size=97)
        for tier in sea.fs.hierarchy.cache_tiers:
            tier.wipe()
        sea.fs.resolver.invalidate_all()
        pipe = DataPipeline(sea, "c", batch_size=2, seq_len=32,
                            evict_consumed=False)
        batches = list(pipe)
        pipe.close()
        assert len(batches) == (5 * 4096) // (2 * 33)
        assert pipe.stats.cache_misses > 0
        # the numbered shard sequence is exactly what the predictor eats
        assert wait_until(
            lambda: sea.fs.telemetry.readahead_predictions > 0
        )


# ------------------------------------------------------------- simulator
def test_simulator_readahead_overlaps_cold_reads():
    from repro.core.model import ClusterSpec, MiB, Workload
    from repro.core.simulator import Simulator

    cl = ClusterSpec(c=2, p=2)
    w = Workload(B=16, F=256 * MiB, n=2)
    kw = dict(compute_s_per_iter=0.1)
    base = Simulator(cl, w, "sea", **kw).run()
    ra = Simulator(cl, w, "sea", readahead=True, **kw).run()
    assert ra.readahead_hits > 0
    assert ra.readahead_staged >= ra.readahead_hits
    # cold-input stalls move off the critical path: the app finishes
    # strictly earlier, and staging hides under compute so the full
    # drain does too
    assert ra.app_done_s < base.app_done_s
    assert ra.makespan < base.makespan
