"""CI perf-regression gate for the placement/multiproc/resolve/transfer/
readahead/extent/federation/training/seacheck/chaos benchmarks.

Compares a freshly produced ``BENCH_pr10.json`` (written by
``placement_bench --json`` + ``multiproc_bench --json`` +
``resolve_bench --json`` + ``transfer_bench --json`` +
``readahead_bench --json`` + ``extent_bench --json`` +
``federation_bench --json`` + ``training_bench --json`` +
``seacheck_bench --json`` + ``chaos_bench --json``, merged by the CI
workflow) against the committed ``benchmarks/BENCH_baseline.json``.

The structural gates are machine-independent and strict:
  * select() must stay O(1)-flat: ledger select cost at the largest
    population <= FLATNESS_X times its cost at the smallest,
  * multi-process run never over-committed the capped root,
  * multi-process aggregate throughput did not collapse (>= 0.5x 1-proc),
  * the cached resolver's hit path stays flat across root counts,
  * transfer engine moves a large file at parity with shutil.copyfile
    (ratio >= MIN_TRANSFER_RATIO) and pooled prefetch staging overlaps
    > MIN_OVERLAP_SPEEDUP x over serial copies. (Transfer gates are
    pure ratios — absolute throughputs are machine-dependent, so no
    baseline comparison is applied to them.)
  * seacheck: the SEACHECK=1 runtime lock-order detector keeps the
    instrumented tier-1 subset under MAX_SEACHECK_OVERHEAD_X x the
    uninstrumented wall-clock (both legs are real pytest subprocesses),
  * predictive readahead: cold sequential block reads >= MIN_SEQ_SPEEDUP x
    faster with readahead on (modelled tier bandwidths: deterministic),
    and wasted-prefetch bytes < MAX_WASTED_RATIO of staged bytes on a
    random-access permutation.
  * extent plane: cold time-to-first-cached-byte on a large file
    >= MIN_TTFB_SPEEDUP x faster with the extent map than whole-file
    staging (both paced by the same token-bucket cap: deterministic),
    and a scan of a file 4x the cache tier stays bit-exact, never
    over-commits the ledger, actually punches cold extents, and keeps
    >= MIN_HOT_CHUNK_RATIO of chunks served from staged extents.
  * federation: a second node reading a sibling-staged working set is
    >= MIN_PEER_SPEEDUP x faster than the identical cold-from-base run
    (modelled tier bandwidths, real peer->cache token-bucket cap),
    every warm read is a peer hit, and with peers killed mid-pull every
    read still returns bit-exact base content with zero partial or tmp
    files left in the puller's cache.
  * training I/O: blocking checkpoint saves (the seed path,
    ``checkpoint_workers=1``) cost >= MIN_BLOCKING_OVERHEAD x the
    no-checkpoint step loop while async saves of the same modelled
    bytes stay under MAX_ASYNC_OVERHEAD x (the write disappeared behind
    compute), the double-buffered device feed beats the unbuffered
    put-then-compute loop >= MIN_FEED_SPEEDUP x, and a sharded save
    writes each shard exactly once (unique manifest files, payload
    within MAX_SHARDED_RATIO of the logical bytes, bit-exact restore).

Every failure message is prefixed with its ``[section]`` so CI logs
name the benchmark that tripped the gate, and sections reporting an
``elapsed_s`` get their wall-clock printed so slow gates are
attributable.

Absolute timings vary with runner hardware, so against the baseline only a
gross regression fails: any placement or resolver row more than
ABS_TOLERANCE_X slower than the committed number.

``python -m benchmarks.check_regression BENCH_pr10.json [baseline.json]``
"""

from __future__ import annotations

import json
import os
import sys

FLATNESS_X = 3.0      # ledger select at 10k files vs at 100 files
MIN_SCALING = 0.5     # multiproc aggregate vs single-process
ABS_TOLERANCE_X = 5.0  # gross-regression multiplier vs committed baseline
RESOLVE_FLATNESS_X = 3.0    # cached hit path: widest layout vs narrowest
MIN_TRANSFER_RATIO = 0.85   # engine vs shutil.copyfile large-file parity:
                            # both bottom out at the same zero-copy syscalls,
                            # so a genuine chunk-loop regression measures
                            # 0.6-0.75 while runner noise stays within ±0.1
MIN_OVERLAP_SPEEDUP = 1.5   # pooled staging vs serial copies (latency-bound)
MIN_SEQ_SPEEDUP = 2.0       # cold sequential reads: readahead on vs off
MAX_WASTED_RATIO = 0.20     # wasted / staged speculative bytes, random access
MIN_TTFB_SPEEDUP = 5.0      # cold TTFB: one-extent fault vs whole-file stage
MIN_HOT_CHUNK_RATIO = 0.5   # bigger-than-tier scan chunks served hot
MIN_PEER_SPEEDUP = 2.0      # warm-peer read vs cold-from-base, same caps
MIN_BLOCKING_OVERHEAD = 2.0  # blocking-save step loop vs no-ckpt loop
MAX_ASYNC_OVERHEAD = 1.15   # async-save step loop vs no-ckpt loop
MIN_FEED_SPEEDUP = 1.5      # double-buffered device feed vs unbuffered
MAX_SHARDED_RATIO = 1.01    # ckpt payload / logical state bytes (npy headers)
MAX_SEACHECK_OVERHEAD_X = 2.0  # SEACHECK=1 tier-1 subset vs uninstrumented
MAX_DEGRADED_OVERHEAD_X = 10.0  # killed-root read pass vs healthy warm pass
MAX_DEADLINE_GRACE_S = 2.0  # scheduling slop on the hung-copy abort

_BASELINE = os.path.join(os.path.dirname(__file__), "BENCH_baseline.json")


def _row(rows: list[dict], name: str) -> dict | None:
    return next((r for r in rows if r["name"] == name), None)


def check(current: dict, baseline: dict | None) -> list[str]:
    failures: list[str] = []

    def fail(section: str, msg: str) -> None:
        failures.append(f"[{section}] {msg}")

    rows = current["placement"]["rows"]

    sizes = sorted(
        int(r["name"].rsplit("_", 1)[1][:-1])
        for r in rows
        if r["name"].startswith("placement_select_ledger_")
    )
    small, big = sizes[0], sizes[-1]
    s_small = _row(rows, f"placement_select_ledger_{small}f")["us_per_call"]
    s_big = _row(rows, f"placement_select_ledger_{big}f")["us_per_call"]
    if s_big > FLATNESS_X * s_small:
        fail(
            "placement",
            f"select() not O(1)-flat: {s_big}us at {big} files vs "
            f"{s_small}us at {small} (allowed {FLATNESS_X}x)",
        )

    for scale in current["multiproc"]["scales"]:
        if scale["overcommitted"]:
            fail(
                "multiproc",
                f"capped root over-committed at {scale['n_procs']} procs: "
                f"{scale['cache_used_bytes']} > {scale['capacity']}",
            )
        if scale["files_placed"] != scale["files_written"]:
            fail(
                "multiproc",
                f"lost files at {scale['n_procs']} procs: "
                f"{scale['files_written'] - scale['files_placed']}",
            )
    top = current["multiproc"]["scales"][-1]
    if top["scaling_vs_1proc"] < MIN_SCALING:
        fail(
            "multiproc",
            f"throughput collapsed: {top['scaling_vs_1proc']}x "
            f"at {top['n_procs']} procs < {MIN_SCALING}x",
        )

    resolver = current.get("resolver")
    if resolver is None:
        fail("resolver", "section missing (resolve_bench not run)")
    else:
        flatness = resolver["hit_flatness"]
        if flatness > RESOLVE_FLATNESS_X:
            fail(
                "resolver",
                f"hit path not flat across root counts: "
                f"{flatness}x (allowed {RESOLVE_FLATNESS_X}x)",
            )

    transfer = current.get("transfer")
    if transfer is None:
        fail("transfer", "section missing (transfer_bench not run)")
    else:
        ratio = transfer["large_ratio"]
        if ratio < MIN_TRANSFER_RATIO:
            fail(
                "transfer",
                f"engine large-file throughput {ratio}x of shutil "
                f"< required {MIN_TRANSFER_RATIO}x parity",
            )
        overlap = transfer["overlap_speedup"]
        if overlap <= MIN_OVERLAP_SPEEDUP:
            fail(
                "transfer",
                f"concurrent-prefetch overlap {overlap}x <= required "
                f"{MIN_OVERLAP_SPEEDUP}x over serial staging",
            )

    readahead = current.get("readahead")
    if readahead is None:
        fail("readahead", "section missing (readahead_bench not run)")
    else:
        seq = readahead["cold_seq_speedup"]
        if seq < MIN_SEQ_SPEEDUP:
            fail(
                "readahead",
                f"cold sequential readahead speedup {seq}x "
                f"< required {MIN_SEQ_SPEEDUP}x",
            )
        wasted = readahead["wasted_ratio"]
        if wasted >= MAX_WASTED_RATIO:
            fail(
                "readahead",
                f"wasted-prefetch ratio {wasted} on random access "
                f">= allowed {MAX_WASTED_RATIO}",
            )

    extent = current.get("extent")
    if extent is None:
        fail("extent", "section missing (extent_bench not run)")
    else:
        ttfb = extent["ttfb_speedup"]
        if ttfb < MIN_TTFB_SPEEDUP:
            fail(
                "extent",
                f"cold-TTFB speedup {ttfb}x < required {MIN_TTFB_SPEEDUP}x",
            )
        if not extent["scan_bitexact"]:
            fail(
                "extent", "bigger-than-tier extent scan returned corrupted bytes"
            )
        if extent["scan_overcommitted"]:
            fail(
                "extent",
                "bigger-than-tier extent scan over-committed the cache tier",
            )
        if extent["scan_extents_punched"] <= 0:
            fail(
                "extent",
                "bigger-than-tier extent scan never punched a cold extent",
            )
        hot = extent["scan_hot_chunk_ratio"]
        if hot < MIN_HOT_CHUNK_RATIO:
            fail(
                "extent",
                f"bigger-than-tier scan hot-chunk ratio {hot} "
                f"< required {MIN_HOT_CHUNK_RATIO}",
            )

    federation = current.get("federation")
    if federation is None:
        fail("federation", "section missing (federation_bench not run)")
    else:
        peer = federation["peer_speedup"]
        if peer < MIN_PEER_SPEEDUP:
            fail(
                "federation",
                f"warm-peer read speedup {peer}x over cold base "
                f"< required {MIN_PEER_SPEEDUP}x",
            )
        hits = federation["peer_hits"]
        if federation.get("warm_torn_reads", 0) or hits <= 0:
            fail(
                "federation",
                f"warm run not served from peers: hits={hits} "
                f"torn={federation.get('warm_torn_reads', 0)}",
            )
        if federation["fault_torn_reads"]:
            fail(
                "federation",
                f"peer death mid-pull returned corrupted reads: "
                f"{federation['fault_torn_reads']} files",
            )
        if federation["fault_cache_residue"]:
            fail(
                "federation",
                f"peer death mid-pull leaked partial/tmp files: "
                f"{federation['fault_cache_residue']}",
            )
        if federation["fault_fallbacks"] <= 0:
            fail(
                "federation",
                "fault run recorded no peer_fallbacks "
                "(injection did not reach the pull path)",
            )

    training = current.get("training")
    if training is None:
        fail("training", "section missing (training_bench not run)")
    else:
        blocking = training["blocking_overhead_x"]
        if blocking < MIN_BLOCKING_OVERHEAD:
            fail(
                "training",
                f"blocking-save overhead {blocking}x vs no-ckpt loop "
                f"< required {MIN_BLOCKING_OVERHEAD}x (the modelled "
                f"checkpoint bytes are too cheap to gate overlap)",
            )
        async_x = training["async_overhead_x"]
        if async_x > MAX_ASYNC_OVERHEAD:
            fail(
                "training",
                f"async-save overhead {async_x}x vs no-ckpt loop "
                f"> allowed {MAX_ASYNC_OVERHEAD}x (writes not hidden "
                f"behind compute)",
            )
        feed = training["feed_speedup"]
        if feed < MIN_FEED_SPEEDUP:
            fail(
                "training",
                f"double-buffered device feed {feed}x over unbuffered "
                f"< required {MIN_FEED_SPEEDUP}x",
            )
        if not training["sharded_unique_files"]:
            fail("training", "sharded save wrote a shard file twice")
        ratio = training["sharded_write_ratio"]
        if not 1.0 <= ratio <= MAX_SHARDED_RATIO:
            fail(
                "training",
                f"sharded save payload/logical ratio {ratio} outside "
                f"[1.0, {MAX_SHARDED_RATIO}] (shards duplicated or lost)",
            )
        if not training["sharded_roundtrip_ok"]:
            fail("training", "sharded checkpoint did not restore bit-exact")

    seacheck = current.get("seacheck")
    if seacheck is None:
        fail("seacheck", "section missing (seacheck_bench not run)")
    else:
        overhead = seacheck["overhead_x"]
        if overhead >= MAX_SEACHECK_OVERHEAD_X:
            fail(
                "seacheck",
                f"SEACHECK=1 instrumentation overhead {overhead}x "
                f">= allowed {MAX_SEACHECK_OVERHEAD_X}x (the instrumented "
                f"matrix leg is only viable while detection stays cheap)",
            )

    chaos = current.get("chaos")
    if chaos is None:
        fail("chaos", "section missing (chaos_bench not run)")
    else:
        seed = chaos.get("seed", "?")
        if chaos["torn_reads"]:
            fail(
                "chaos",
                f"killed-root run returned corrupted reads: "
                f"{chaos['torn_reads']} files (seed={seed})",
            )
        if chaos["open_failures"]:
            fail(
                "chaos",
                f"{chaos['open_failures']} opens surfaced the dead root "
                f"to the application instead of degrading (seed={seed})",
            )
        if chaos["degraded_reads"] <= 0 or not chaos["breaker_open_after_kill"]:
            fail(
                "chaos",
                f"kill did not register: degraded_reads="
                f"{chaos['degraded_reads']} breaker_open="
                f"{chaos['breaker_open_after_kill']} (seed={seed})",
            )
        overhead = chaos["degraded_overhead_x"]
        if overhead > MAX_DEGRADED_OVERHEAD_X:
            fail(
                "chaos",
                f"degraded-mode read overhead {overhead}x vs healthy "
                f"> allowed {MAX_DEGRADED_OVERHEAD_X}x (seed={seed})",
            )
        if not chaos["readmitted"]:
            fail(
                "chaos",
                f"breaker never re-admitted the recovered root within "
                f"{chaos.get('recovery_s', '?')}s (seed={seed})",
            )
        limit = chaos["deadline_s"] + MAX_DEADLINE_GRACE_S
        if not chaos["deadline_aborted"] or chaos["deadline_abort_s"] > limit:
            fail(
                "chaos",
                f"hung copy abort took {chaos['deadline_abort_s']}s "
                f"(aborted={chaos['deadline_aborted']}) > deadline "
                f"{chaos['deadline_s']}s + {MAX_DEADLINE_GRACE_S}s grace "
                f"(seed={seed})",
            )
        if chaos["reservation_leaked"]:
            fail(
                "chaos",
                f"failure paths leaked {chaos['reservation_leaked']} "
                f"reserved bytes (seed={seed})",
            )

    if baseline is not None:
        base_rows = baseline["placement"]["rows"]
        for r in rows:
            b = _row(base_rows, r["name"])
            if b and r["us_per_call"] > ABS_TOLERANCE_X * b["us_per_call"]:
                fail(
                    "placement",
                    f"{r['name']}: {r['us_per_call']}us > "
                    f"{ABS_TOLERANCE_X}x baseline {b['us_per_call']}us",
                )
        base_resolver = baseline.get("resolver")
        if resolver is not None and base_resolver is not None:
            for r in resolver["rows"]:
                b = _row(base_resolver["rows"], r["name"])
                if b and r["us_per_call"] > ABS_TOLERANCE_X * b["us_per_call"]:
                    fail(
                        "resolver",
                        f"{r['name']}: {r['us_per_call']}us > "
                        f"{ABS_TOLERANCE_X}x baseline {b['us_per_call']}us",
                    )
    return failures


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: check_regression.py BENCH_pr10.json [baseline.json]")
        raise SystemExit(2)
    with open(argv[0]) as f:
        current = json.load(f)
    baseline_path = argv[1] if len(argv) > 1 else _BASELINE
    baseline = None
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f)
    else:
        print(f"note: no baseline at {baseline_path}; structural gates only")
    timed = [
        (name, section["elapsed_s"])
        for name, section in current.items()
        if isinstance(section, dict) and "elapsed_s" in section
    ]
    for name, secs in sorted(timed, key=lambda t: -t[1]):
        print(f"timing: [{name}] {secs}s")
    failures = check(current, baseline)
    for msg in failures:
        print(f"REGRESSION: {msg}")
    if not failures:
        print("perf gate passed")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
