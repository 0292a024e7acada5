"""Resolution hot-path benchmark: the cached resolver across root counts.

Resolved-read latency must be independent of root count on the hit path
(gated flat from 3 tiers × 1 root to 3 tiers × 4 roots).

Files are populated on the BASE tier (the worst case for a probe cascade:
every cache root answers ENOENT before the base tier hits), mirroring the
read-heavy neuroimaging workloads of the HSM follow-up paper, where
metadata-path latency dominates.

Three measurements per (tiers × roots) layout:
  resolve_cached   — ``SeaFS.resolve_read`` with the warm location index
                     and the default verify trust window (pure dict
                     lookup; data-touching ops re-verify via their own
                     ENOENT)
  resolve_verified — ``resolver_verify_window_s=0``: strict verify-on-hit,
                     one ``lstat`` per hit regardless of root count
  stat_cached      — end-to-end ``SeaFS.stat`` through the warm index

``PYTHONPATH=src python -m benchmarks.resolve_bench [--json PATH]``
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

#: (label, roots-per-cache-tier). 3 tiers always; the base keeps one root.
_LAYOUTS = (("3x1", 1), ("3x2", 2), ("3x4", 4))
_N_FILES = 256


def _config(workdir: str, roots_per_tier: int, verify_window_s: float = 0.05) -> SeaConfig:
    def roots(tag: str) -> tuple[str, ...]:
        return tuple(
            os.path.join(workdir, f"{tag}{i}") for i in range(roots_per_tier)
        )

    return SeaConfig(
        mount=os.path.join(workdir, "mount"),
        tiers=[
            TierSpec(name="tmpfs", roots=roots("t")),
            TierSpec(name="disk", roots=roots("d")),
            TierSpec(
                name="pfs", roots=(os.path.join(workdir, "pfs"),), persistent=True
            ),
        ],
        max_file_size=1 << 16,
        n_procs=2,
        resolver_verify_window_s=verify_window_s,
    )


def _populate_base(workdir: str, n_files: int) -> list[str]:
    """Drop ``n_files`` small files directly on the base tier (cold input
    data, per the paper: inputs start on the PFS)."""
    base = os.path.join(workdir, "pfs")
    keys = []
    for i in range(n_files):
        key = f"inputs/d{i % 16:02d}/f{i}.bin"
        real = os.path.join(base, key)
        os.makedirs(os.path.dirname(real), exist_ok=True)
        with open(real, "wb") as f:
            f.write(b"x" * 64)
        keys.append(key)
    return keys


def _time_resolve(fs: SeaFS, keys: list[str], n_rounds: int) -> float:
    """Mean seconds per ``resolve_read`` over the key population."""
    for key in keys:
        assert fs.resolve_read(key) is not None  # warm (and sanity)
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        for key in keys:
            fs.resolve_read(key)
    return (time.perf_counter() - t0) / (n_rounds * len(keys))


def _time_stat(fs: SeaFS, keys: list[str], n_rounds: int) -> float:
    paths = [os.path.join(fs.mount, k) for k in keys]
    fs.stat(paths[0])  # warm
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        for p in paths:
            fs.stat(p)
    return (time.perf_counter() - t0) / (n_rounds * len(paths))


def bench_resolver():
    rows = []
    for label, roots_per_tier in _LAYOUTS:
        workdir = tempfile.mkdtemp(prefix="sea_resolve_bench_")
        try:
            keys = _populate_base(workdir, _N_FILES)
            fs_cached = SeaFS(_config(workdir, roots_per_tier))
            fs_strict = SeaFS(_config(workdir, roots_per_tier, verify_window_s=0.0))

            s_cached = _time_resolve(fs_cached, keys, n_rounds=20)
            s_strict = _time_resolve(fs_strict, keys, n_rounds=10)
            s_stat = _time_stat(fs_cached, keys, n_rounds=10)

            rows.append({
                "name": f"resolve_cached_{label}",
                "us_per_call": round(s_cached * 1e6, 2),
                "derived": "",
            })
            rows.append({
                "name": f"resolve_verified_{label}",
                "us_per_call": round(s_strict * 1e6, 2),
                "derived": "",
            })
            rows.append({
                "name": f"stat_cached_{label}",
                "us_per_call": round(s_stat * 1e6, 2),
                "derived": "",
            })
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return rows


ALL_RESOLVE_BENCHES = [bench_resolver]


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    json_path = None
    if "--json" in argv:
        if argv.index("--json") + 1 >= len(argv):
            print("usage: resolve_bench [--json PATH]")
            raise SystemExit(2)
        json_path = argv[argv.index("--json") + 1]
    t_start = time.perf_counter()
    print("name,us_per_call,derived")
    rows = bench_resolver()
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{row['derived']}")

    def _us(name: str) -> float:
        return next(r for r in rows if r["name"] == name)["us_per_call"]

    # acceptance: hit path independent of root count (flat across layouts)
    big, small = _LAYOUTS[-1][0], _LAYOUTS[0][0]
    flatness = _us(f"resolve_cached_{big}") / _us(f"resolve_cached_{small}")
    print(f"acceptance_hit_flatness_{big}_vs_{small},{flatness:.2f},<=3x_required")
    ok = flatness <= 3.0
    if json_path:
        with open(json_path, "w") as f:
            json.dump(
                {
                    "rows": rows,
                    "hit_flatness": round(flatness, 2),
                    "elapsed_s": round(time.perf_counter() - t_start, 2),
                },
                f,
                indent=2,
            )
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
