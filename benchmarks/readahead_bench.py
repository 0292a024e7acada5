"""Adaptive-read-path benchmark: predictive readahead + open fast path.

Two acceptance targets, plus one measurement:

* **Cold sequential block processing** (the Big Brain-style workload of
  the HSM follow-up paper: a pipeline walks numbered blocks it has
  never seen, computing on each). The storage speeds are *modelled*, so
  the measurement is hardware-independent and deterministic: an
  application read pays ``bytes / BW`` of its serving tier (slow PFS vs
  fast cache), and speculative staging is paced by the engine's real
  token-bucket throttle (``transfer_bandwidth_caps``) at streaming
  bandwidth. With ``readahead=True`` the predictor must overlap staging
  with compute and serve the reads hot: wall-clock >= 2x faster than
  ``readahead=False`` (median of 3 runs each).
* **Speculation discipline** — on a random-access permutation of the
  same blocks the predictor must keep wasted-prefetch bytes (staged but
  never read) under 20% of staged bytes.
* **Open fast path** — per-call bookkeeping overhead of a read-hit
  ``open``/close (Sea's Python work with the ``open(2)`` syscall
  stubbed out), reported ungated.

``PYTHONPATH=src python -m benchmarks.readahead_bench [--json PATH]``
prints the same ``name,us_per_call,derived`` CSV as the other benches;
``--json`` dumps rows + derived ratios for ``benchmarks.check_regression``.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import sys
import tempfile
import time

from repro.core import SeaConfig, SeaFS, TierSpec

_BLOCK_BYTES = 1 << 20       # one processing block
_N_BLOCKS = 32               # a short Big Brain-style sweep
_APP_CHUNK = 512 << 10       # application read granularity (2 chunks per
                             # block: enough to model streaming, few
                             # enough that per-sleep timer overshoot
                             # cannot eat the cache-read margin)
_BW_PFS = 16e6               # modelled cold-tier read bandwidth (bytes/s)
                             # — far enough below the cache model that
                             # timer-slack jitter (~5-10ms/block on busy
                             # runners) cannot eat the >=2x gate margin
_BW_CACHE = 512e6            # modelled cache-tier read bandwidth
_BW_STAGE = 128e6            # staging stream cap (token-bucket, real)
_COMPUTE_S = 0.015           # per-block compute the staging hides under
_SEQ_RUNS = 3                # median-of
_MIN_SEQ_SPEEDUP = 2.0
_MAX_WASTED_RATIO = 0.20
_FASTPATH_CALLS = 1000
_FASTPATH_ROUNDS = 9


def _config(workdir: str, *, readahead: bool) -> SeaConfig:
    return SeaConfig(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(name="tmpfs", roots=(os.path.join(workdir, "t0"),)),
            TierSpec(
                name="pfs", roots=(os.path.join(workdir, "pfs"),), persistent=True
            ),
        ],
        max_file_size=2 * _BLOCK_BYTES,
        readahead=readahead,
        transfer_bandwidth_caps={"pfs->*": _BW_STAGE},
    )


def _seed_blocks(workdir: str) -> None:
    root = os.path.join(workdir, "pfs")
    os.makedirs(root, exist_ok=True)
    blob = os.urandom(_BLOCK_BYTES)
    for i in range(_N_BLOCKS):
        with open(os.path.join(root, f"block_{i:05d}.bin"), "wb") as f:
            f.write(blob)


def _paced_read(f, tier: str) -> int:
    """Read a whole block at _APP_CHUNK granularity, sleeping out the
    modelled bandwidth of the serving tier (the real I/O inside the
    container is page-cache-fast either way; the model is what makes the
    measurement hardware-independent)."""
    bw = _BW_PFS if tier == "pfs" else _BW_CACHE
    total = 0
    while True:
        chunk = f.read(_APP_CHUNK)
        if not chunk:
            return total
        total += len(chunk)
        time.sleep(len(chunk) / bw)


def _run_sequential(workdir: str, *, readahead: bool) -> tuple[float, SeaFS]:
    fs = SeaFS(_config(workdir, readahead=readahead))
    t0 = time.perf_counter()
    for i in range(_N_BLOCKS):
        p = os.path.join(fs.mount, f"block_{i:05d}.bin")
        with fs.open(p, "rb") as f:
            _paced_read(f, f.sea_tier)
        time.sleep(_COMPUTE_S)  # per-block compute (staging overlaps here)
    dt = time.perf_counter() - t0
    fs.prefetcher.stop()
    fs.transfer.close()
    return dt, fs


def bench_sequential(workdir: str) -> tuple[list[dict], float]:
    _seed_blocks(workdir)
    cold: list[float] = []
    warm: list[float] = []
    for _ in range(_SEQ_RUNS):
        for enabled, times in ((False, cold), (True, warm)):
            # fresh cache + fresh predictor per run: every run is cold
            shutil.rmtree(os.path.join(workdir, "t0"), ignore_errors=True)
            dt, _fs = _run_sequential(workdir, readahead=enabled)
            times.append(dt)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    speedup = med(cold) / med(warm)
    rows = [
        {
            "name": f"seq_cold_read_{_N_BLOCKS}x{_BLOCK_BYTES >> 20}MiB",
            "us_per_call": round(med(cold) * 1e6 / _N_BLOCKS, 2),
            "derived": "readahead=off",
        },
        {
            "name": f"seq_readahead_{_N_BLOCKS}x{_BLOCK_BYTES >> 20}MiB",
            "us_per_call": round(med(warm) * 1e6 / _N_BLOCKS, 2),
            "derived": f"readahead=on speedup={speedup:.2f}x",
        },
    ]
    return rows, speedup


def bench_random_waste(workdir: str) -> tuple[list[dict], float]:
    _seed_blocks(workdir)
    shutil.rmtree(os.path.join(workdir, "t0"), ignore_errors=True)
    fs = SeaFS(_config(workdir, readahead=True))
    order = list(range(_N_BLOCKS))
    random.Random(11).shuffle(order)
    for i in order:
        p = os.path.join(fs.mount, f"block_{i:05d}.bin")
        with fs.open(p, "rb") as f:
            f.read()
        time.sleep(0.002)  # give speculation time to be wrong
    time.sleep(0.2)  # let in-flight staging settle
    fs.prefetcher.stop()  # pending predictions settle as waste
    snap = fs.telemetry.snapshot()
    fs.transfer.close()
    staged = snap["readahead_staged_bytes"]
    wasted = snap["readahead_wasted_bytes"]
    ratio = (wasted / staged) if staged else 0.0
    rows = [
        {
            "name": f"random_access_staged_{_N_BLOCKS}blk",
            "us_per_call": float(staged),
            "derived": f"wasted={wasted} ratio={ratio:.2f}",
        }
    ]
    return rows, ratio


def _time_loop(fn) -> float:
    t0 = time.perf_counter()
    for _ in range(_FASTPATH_CALLS):
        fn()
    return (time.perf_counter() - t0) * 1e6 / _FASTPATH_CALLS


class _FakeRaw:
    """Stand-in for the object ``io.open`` returns: just enough surface
    for ``_SeaFile.close`` (``tell``/``close``)."""

    __slots__ = ()

    def tell(self):
        return 0

    def close(self):
        pass


def bench_fastpath(workdir: str) -> list[dict]:
    """Per-call bookkeeping overhead of a read-hit open/close.

    The ``open(2)`` syscall in sandboxed kernels is bursty at the
    hundreds-of-µs scale — the same magnitude as the overhead being
    measured — so the syscall itself is stubbed out
    (``repro.core.seafs.io`` swapped for a fake whose ``open`` returns a
    no-op file). What remains is exactly Sea's per-open Python work
    (resolution, counts, telemetry): deterministic and
    hardware-independent, in the same spirit as the modelled-bandwidth
    sequential workload."""
    import gc
    import types

    from repro.core import seafs as seafs_mod

    wd = os.path.join(workdir, "fp")
    shutil.rmtree(wd, ignore_errors=True)
    fs = SeaFS(_config(wd, readahead=False))
    p = os.path.join(fs.mount, "hot.bin")
    with fs.open(p, "wb") as f:
        f.write(b"x" * 4096)
    for _ in range(300):  # warmup (and prime the resolver entry)
        fs.open(p, "rb").close()
    fake_raw = _FakeRaw()
    fake_io = types.SimpleNamespace(open=lambda *a, **kw: fake_raw)
    times: list[float] = []
    orig_io, seafs_mod.io = seafs_mod.io, fake_io
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        # median-of-rounds: residual interpreter noise (GC is off, but
        # timers/threads remain) is discarded with the spike rounds
        for _ in range(_FASTPATH_ROUNDS):
            times.append(_time_loop(lambda: fs.open(p, "rb").close()))
    finally:
        seafs_mod.io = orig_io
        if gc_was_enabled:
            gc.enable()
    assert fs.telemetry.snapshot()["fastpath_opens"] > 0
    fs.transfer.close()
    return [
        {
            "name": "open_read_hit_fastpath_overhead",
            "us_per_call": round(sorted(times)[len(times) // 2], 2),
            "derived": "",
        },
    ]


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    json_path = None
    if "--json" in argv:
        if argv.index("--json") + 1 >= len(argv):
            print("usage: readahead_bench [--json PATH]")
            raise SystemExit(2)
        json_path = argv[argv.index("--json") + 1]

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="sea_readahead_bench_")
    try:
        print("name,us_per_call,derived")
        seq_rows, speedup = bench_sequential(workdir)
        waste_rows, wasted_ratio = bench_random_waste(workdir)
        fp_rows = bench_fastpath(workdir)
        rows = seq_rows + waste_rows + fp_rows
        for row in rows:
            print(f"{row['name']},{row['us_per_call']},{row['derived']}")
        print(
            f"acceptance_seq_speedup,{speedup:.2f},>={_MIN_SEQ_SPEEDUP}x_required"
        )
        print(
            f"acceptance_wasted_ratio,{wasted_ratio:.2f},"
            f"<{_MAX_WASTED_RATIO}_required"
        )
        ok = speedup >= _MIN_SEQ_SPEEDUP and wasted_ratio < _MAX_WASTED_RATIO
        if json_path:
            with open(json_path, "w") as f:
                json.dump(
                    {
                        "rows": rows,
                        "cold_seq_speedup": round(speedup, 2),
                        "wasted_ratio": round(wasted_ratio, 3),
                        "elapsed_s": round(
                            time.perf_counter() - t_start, 2
                        ),
                    },
                    f,
                    indent=2,
                )
        raise SystemExit(0 if ok else 1)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
