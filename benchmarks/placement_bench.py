"""Placement hot-path benchmark: the capacity ledger across cache sizes.

Placement-decision cost must be independent of cached-file count on a
capped root (the regression gate holds ``select()`` flat from 100 to 10k
files).

Two measurements per population size:
  placement_select   — ``PlacementPolicy.select()`` alone (the eligibility
                       check every intercepted ``open(.., "w")`` pays)
  open_write_close   — end-to-end SeaFS ``open``/``write``/``close``/
                       ``remove`` of a fresh key under the mount

``PYTHONPATH=src python -m benchmarks.placement_bench [--json PATH]``
prints the same ``name,us_per_call,derived`` CSV as the other benches;
``--json`` additionally dumps the rows for the CI regression gate
(``benchmarks.check_regression``).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

from repro.core import SeaConfig, SeaFS, TierSpec

_POPULATIONS = (100, 1000, 10000)


def _populate(root: str, n_files: int) -> None:
    """Drop ``n_files`` small files under ``root`` (64 dirs, like a real
    scattered cache) for the ledger's reconcile walk to account."""
    payload = b"x" * 64
    for i in range(n_files):
        d = os.path.join(root, f"d{i % 64:02d}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"f{i}.bin"), "wb") as f:
            f.write(payload)


def _config(workdir: str) -> SeaConfig:
    return SeaConfig(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(
                name="cache",
                roots=(os.path.join(workdir, "cache"),),
                capacity=1 << 30,  # capped -> eligibility must count used bytes
            ),
            TierSpec(
                name="pfs",
                roots=(os.path.join(workdir, "pfs"),),
                persistent=True,
            ),
        ],
        max_file_size=1 << 16,
        n_procs=2,
        ledger_reconcile_interval_s=1e9,  # isolate the hot path from reconciles
    )


def _time_select(fs: SeaFS, n_calls: int) -> float:
    """Mean seconds per ``policy.select()`` (the placement decision)."""
    fs.policy.select()  # warm (triggers the ledger's one reconcile walk)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        fs.policy.select()
    return (time.perf_counter() - t0) / n_calls


def _time_open(fs: SeaFS, n_calls: int) -> float:
    """Mean seconds per open/write/close/remove of a fresh key."""
    fs.policy.select()  # warm
    t0 = time.perf_counter()
    for i in range(n_calls):
        p = os.path.join(fs.mount, f"bench_{i}.bin")
        with fs.open(p, "wb") as f:
            f.write(b"y" * 128)
        fs.remove(p)
    return (time.perf_counter() - t0) / n_calls


def bench_placement_ledger(quick: bool = True):
    rows = []
    # the full sweep IS the quick sweep: wall time is bounded either way
    del quick
    for n_files in _POPULATIONS:
        workdir = tempfile.mkdtemp(prefix="sea_placement_bench_")
        try:
            cache_root = os.path.join(workdir, "cache")
            os.makedirs(cache_root, exist_ok=True)
            _populate(cache_root, n_files)
            fs = SeaFS(_config(workdir))
            rows.append({
                "name": f"placement_select_ledger_{n_files}f",
                "us_per_call": round(_time_select(fs, 2000) * 1e6, 2),
                "derived": "",
            })
            rows.append({
                "name": f"open_write_close_ledger_{n_files}f",
                "us_per_call": round(_time_open(fs, 500) * 1e6, 2),
                "derived": "",
            })
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return rows


ALL_PLACEMENT_BENCHES = [bench_placement_ledger]


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    json_path = None
    if "--json" in argv:
        if argv.index("--json") + 1 >= len(argv):
            print("usage: placement_bench [--json PATH]")
            raise SystemExit(2)
        json_path = argv[argv.index("--json") + 1]
    t_start = time.perf_counter()
    print("name,us_per_call,derived")
    rows = bench_placement_ledger(quick=True)
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(
                {
                    "rows": rows,
                    "elapsed_s": round(time.perf_counter() - t_start, 2),
                },
                f,
                indent=2,
            )


if __name__ == "__main__":
    main()
