"""Streaming a file TWICE the cache tier's capacity through the extent
plane.

Whole-file placement cannot serve this workload hot at all: the file
never fits, so every read falls through to the slow base tier. With
``extent_map=True`` the cache holds a *sliding window* of 4 MiB blocks —
each block is faulted once on first touch, served hot for the rest of
its lifetime, and punched back to a hole when the LRU needs room — so
the scan streams through a tier half its size without ever
over-committing the capacity ledger.

The demo seeds a 32 MiB input on the (modelled) PFS, mounts a 16 MiB
cache in front of it, then:

  1. block-scans the whole file sequentially,
  2. random-accesses a handful of offsets (only the touched blocks
     fault — no whole-file stage),

and prints the extent telemetry counters plus the ledger-vs-walk
accounting after each phase.

    PYTHONPATH=src python examples/extent_streaming.py
"""

import os
import random
import shutil
import tempfile

from repro.core import SeaConfig, SeaFS, TierSpec
from repro.core.ledger import scan_root

FILE_BYTES = 32 << 20    # the cold input: 2x the cache tier
EXTENT_BYTES = 4 << 20   # 8 blocks per file
CACHE_CAP = 16 << 20     # the tier the file does NOT fit in
CHUNK = 1 << 20          # application read granularity


def make_config(workdir: str) -> SeaConfig:
    return SeaConfig(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(
                name="fast",
                roots=(os.path.join(workdir, "fast"),),
                capacity=CACHE_CAP,
            ),
            TierSpec(
                name="pfs", roots=(os.path.join(workdir, "pfs"),), persistent=True
            ),
        ],
        max_file_size=FILE_BYTES,
        extent_map=True,          # key -> extent map on the cache tiers
        extent_bytes=EXTENT_BYTES,
        lru_evict=True,           # punch cold extents when the tier is full
    )


def report(fs: SeaFS, phase: str) -> None:
    snap = fs.telemetry.snapshot()
    tier = fs.hierarchy.cache_tiers[0]
    root = tier.roots[0]
    used, walk = tier.used_bytes(root), sum(scan_root(root).values())
    print(f"\n-- {phase} --")
    for k in (
        "extent_hits",
        "extent_misses",
        "extents_staged",
        "extents_punched",
        "extent_promotions",
    ):
        print(f"  {k:20s} {snap[k]}")
    print(
        f"  cache used: ledger={used} walk={walk} cap={CACHE_CAP} "
        f"({'OK' if used == walk <= CACHE_CAP else 'DRIFT'})"
    )


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="sea_extent_demo_")
    try:
        # a cold input that already lives on the base tier (a PFS dataset)
        pfs = os.path.join(workdir, "pfs")
        os.makedirs(pfs)
        data = os.urandom(FILE_BYTES)
        with open(os.path.join(pfs, "dataset.bin"), "wb") as f:
            f.write(data)

        fs = SeaFS(make_config(workdir))
        p = os.path.join(fs.mount, "dataset.bin")
        print(
            f"file={FILE_BYTES >> 20}MiB  cache={CACHE_CAP >> 20}MiB  "
            f"extent={EXTENT_BYTES >> 20}MiB "
            f"({FILE_BYTES // EXTENT_BYTES} blocks)"
        )

        # 1. sequential block scan: every block faults once, then serves
        #    hot; the LRU punches the oldest blocks to stay under cap
        seen = 0
        with fs.open(p, "rb") as f:
            while chunk := f.read(CHUNK):
                assert chunk == data[seen : seen + len(chunk)]
                seen += len(chunk)
        assert seen == FILE_BYTES
        report(fs, f"sequential scan ({seen >> 20} MiB verified)")

        # 2. random access: only the touched blocks fault — a punched
        #    region simply re-faults its one extent, never the whole file
        rng = random.Random(7)
        for _ in range(6):
            off = rng.randrange(FILE_BYTES - CHUNK)
            with fs.open(p, "rb") as f:
                f.seek(off)
                assert f.read(CHUNK) == data[off : off + CHUNK]
        report(fs, "random access (6 x 1 MiB)")

        fs.prefetcher.stop()
        fs.transfer.close()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
