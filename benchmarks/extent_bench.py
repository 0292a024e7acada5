"""Extent-plane benchmark: time-to-first-byte on a cold large file.

Two acceptance targets for the extent-granular data plane (ISSUE 6):

* **Cold TTFB** — the time until the first application chunk of a cold
  PFS-resident file is served *from the cache tier*. Whole-file mode
  must stage the entire file (``stage_to_cache``) before a single
  cached byte exists; extent mode faults exactly one block and serves
  it. Both paths move bytes through the same real ``TransferEngine``
  under the same token-bucket bandwidth cap
  (``transfer_bandwidth_caps``), so the ratio is modelled-deterministic
  and hardware-independent: TTFB speedup >= 5x required (median of 3
  cold runs each; the expected ratio is ~= the extent count).
* **Bigger-than-tier streaming** — a file 4x the cache tier's capacity
  is scanned end-to-end through the extent plane with LRU punch-hole
  eviction. The ledger-tracked usage must never exceed capacity, cold
  extents must actually be punched, reads must be bit-exact, and the
  majority of application chunks must still be served hot (each
  extent is faulted once, then read hot chunk-by-chunk).

``PYTHONPATH=src python -m benchmarks.extent_bench [--json PATH]``
prints the same ``name,us_per_call,derived`` CSV as the other benches;
``--json`` dumps rows + derived ratios for ``benchmarks.check_regression``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

from repro.core import SeaConfig, SeaFS, TierSpec
from repro.core.ledger import scan_root

_FILE_BYTES = 32 << 20        # one cold model-checkpoint-sized input
_EXTENT_BYTES = 2 << 20       # 16 extents per file
_APP_CHUNK = 256 << 10        # application read granularity
_BW_STAGE = 64e6              # staging cap (token-bucket, real): whole-file
                              # staging costs ~0.5s, one extent rides the
                              # burst allowance — the gap under test
_TTFB_RUNS = 3                # median-of
_MIN_TTFB_SPEEDUP = 5.0
_TIER_CAP = 8 << 20           # scan target: file is 4x this capacity
_MIN_HOT_CHUNK_RATIO = 0.5    # scan chunks served from staged extents


def _config(workdir: str, *, extent: bool, capacity: int | None = None,
            lru_evict: bool = False) -> SeaConfig:
    return SeaConfig(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(
                name="fast",
                roots=(os.path.join(workdir, "fast"),),
                capacity=capacity,
            ),
            TierSpec(
                name="pfs", roots=(os.path.join(workdir, "pfs"),), persistent=True
            ),
        ],
        max_file_size=_FILE_BYTES,
        extent_map=extent,
        extent_bytes=_EXTENT_BYTES,
        lru_evict=lru_evict,
        transfer_bandwidth_caps={"pfs->*": _BW_STAGE},
    )


def _seed(workdir: str, key: str, nbytes: int) -> None:
    root = os.path.join(workdir, "pfs")
    os.makedirs(os.path.dirname(os.path.join(root, key)), exist_ok=True)
    with open(os.path.join(root, key), "wb") as f:
        f.write(os.urandom(nbytes))


def _ttfb_whole(workdir: str, key: str) -> float:
    """Cold cached read, whole-file plane: the full file must land on the
    cache tier before the first cached chunk can be served."""
    shutil.rmtree(os.path.join(workdir, "fast"), ignore_errors=True)
    fs = SeaFS(_config(workdir, extent=False))
    fs.prefetcher.stop()
    p = os.path.join(fs.mount, key)
    t0 = time.perf_counter()
    staged = fs.stage_to_cache(key)
    with fs.open(p, "rb") as f:
        chunk = f.read(_APP_CHUNK)
        tier = f.sea_tier
    dt = time.perf_counter() - t0
    assert staged == _FILE_BYTES and len(chunk) == _APP_CHUNK
    assert tier == "fast"
    fs.transfer.close()
    return dt


def _ttfb_extent(workdir: str, key: str) -> float:
    """Cold cached read, extent plane: the first read faults exactly one
    block through the same capped engine and serves it from the cache."""
    shutil.rmtree(os.path.join(workdir, "fast"), ignore_errors=True)
    fs = SeaFS(_config(workdir, extent=True))
    fs.prefetcher.stop()  # no background readahead: pure one-extent fault
    p = os.path.join(fs.mount, key)
    t0 = time.perf_counter()
    with fs.open(p, "rb") as f:
        chunk = f.read(_APP_CHUNK)
    dt = time.perf_counter() - t0
    assert len(chunk) == _APP_CHUNK
    snap = fs.telemetry.snapshot()
    assert snap["extents_staged"] == 1, snap["extents_staged"]
    assert snap["extent_hits"] + snap["extent_misses"] >= 1
    fs.transfer.close()
    return dt


def bench_ttfb(workdir: str) -> tuple[list[dict], float]:
    key = "inputs/checkpoint.bin"
    _seed(workdir, key, _FILE_BYTES)
    whole: list[float] = []
    ext: list[float] = []
    for _ in range(_TTFB_RUNS):
        whole.append(_ttfb_whole(workdir, key))
        ext.append(_ttfb_extent(workdir, key))
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    speedup = med(whole) / med(ext)
    n_ext = _FILE_BYTES // _EXTENT_BYTES
    rows = [
        {
            "name": f"ttfb_whole_file_{_FILE_BYTES >> 20}MiB",
            "us_per_call": round(med(whole) * 1e6, 2),
            "derived": "extent_map=off",
        },
        {
            "name": f"ttfb_extent_{_EXTENT_BYTES >> 20}MiB_of_{n_ext}",
            "us_per_call": round(med(ext) * 1e6, 2),
            "derived": f"extent_map=on speedup={speedup:.2f}x",
        },
    ]
    return rows, speedup


def bench_bigger_than_tier(workdir: str) -> tuple[list[dict], dict]:
    """Sequential scan of a file 4x the cache tier's capacity: extent
    admission + punch-hole eviction keep the ledger under the cap while
    most chunks are still served from staged extents."""
    key = "inputs/oversized.bin"
    _seed(workdir, key, _FILE_BYTES)
    shutil.rmtree(os.path.join(workdir, "fast"), ignore_errors=True)
    fs = SeaFS(_config(workdir, extent=True, capacity=_TIER_CAP, lru_evict=True))
    fs.prefetcher.stop()  # deterministic hit accounting: fault-then-read
    p = os.path.join(fs.mount, key)
    import hashlib

    h_sea, h_base = hashlib.sha256(), hashlib.sha256()
    t0 = time.perf_counter()
    chunks = 0
    with fs.open(p, "rb") as f:
        while True:
            chunk = f.read(_APP_CHUNK)
            if not chunk:
                break
            h_sea.update(chunk)
            chunks += 1
    dt = time.perf_counter() - t0
    with open(os.path.join(workdir, "pfs", key), "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h_base.update(chunk)
    snap = fs.telemetry.snapshot()
    tier = fs.hierarchy.cache_tiers[0]
    used = tier.used_bytes(tier.roots[0])
    scan_used = sum(scan_root(tier.roots[0]).values())
    fs.transfer.close()
    hot_ratio = snap["extent_hits"] / max(1, chunks)
    derived = {
        "bitexact": h_sea.hexdigest() == h_base.hexdigest(),
        "ledger_used": used,
        "scan_used": scan_used,
        "capacity": _TIER_CAP,
        "overcommitted": used > _TIER_CAP or scan_used > _TIER_CAP,
        "extents_punched": snap["extents_punched"],
        "hot_chunk_ratio": round(hot_ratio, 3),
    }
    rows = [
        {
            "name": f"scan_4x_tier_{_FILE_BYTES >> 20}MiB",
            "us_per_call": round(dt * 1e6 / chunks, 2),
            "derived": (
                f"hot_ratio={hot_ratio:.2f} punched={snap['extents_punched']} "
                f"used={used}<=cap={_TIER_CAP}"
            ),
        }
    ]
    return rows, derived


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    json_path = None
    if "--json" in argv:
        if argv.index("--json") + 1 >= len(argv):
            print("usage: extent_bench [--json PATH]")
            raise SystemExit(2)
        json_path = argv[argv.index("--json") + 1]

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="sea_extent_bench_")
    try:
        print("name,us_per_call,derived")
        ttfb_rows, speedup = bench_ttfb(workdir)
        scan_rows, scan = bench_bigger_than_tier(workdir)
        rows = ttfb_rows + scan_rows
        for row in rows:
            print(f"{row['name']},{row['us_per_call']},{row['derived']}")
        print(
            f"acceptance_ttfb_speedup,{speedup:.2f},>={_MIN_TTFB_SPEEDUP}x_required"
        )
        print(
            f"acceptance_scan_ok,{int(not scan['overcommitted'])},"
            f"bitexact={scan['bitexact']} hot_ratio={scan['hot_chunk_ratio']}"
        )
        ok = (
            speedup >= _MIN_TTFB_SPEEDUP
            and scan["bitexact"]
            and not scan["overcommitted"]
            and scan["extents_punched"] > 0
            and scan["hot_chunk_ratio"] >= _MIN_HOT_CHUNK_RATIO
        )
        if json_path:
            with open(json_path, "w") as f:
                json.dump(
                    {
                        "rows": rows,
                        "ttfb_speedup": round(speedup, 2),
                        "scan_bitexact": scan["bitexact"],
                        "scan_overcommitted": scan["overcommitted"],
                        "scan_extents_punched": scan["extents_punched"],
                        "scan_hot_chunk_ratio": scan["hot_chunk_ratio"],
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
