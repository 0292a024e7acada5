"""Readings that the limits of ``correct`` of ``granite-4-h-micro.seq8k``
are set from. Run on the chip.

    python3 chipbench/calibrate_hybrid.py --seeds 2 --controls 1 --faults 1 [--first-seed N]

``calibrate.py train`` for the hybrid cell: for each seed, the program's
readings from the ``train_hybrid`` driver's own set-up (the jitted step,
its state and the device feed at the cell's size) against the float32
reference of ``reference/granite_hybrid.py``: the lower readings. For the
first ``--controls`` seeds also the control (the reference in the
program's place, every matrix product in fp8), and for the first
``--faults`` seeds a planted fault (the reference in the program's place,
the mean taken over half of each batch's rows), each against the same
reference: the upper readings.

Prints one JSON object per reading; with ``--dump DIR`` also writes each
reading's per-leaf numbers there.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def calibrate(args) -> None:
    from chipbench import harness, traffic
    from chipbench.reference import granite_hybrid as ref

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell.load(bench, "granite-4-h-micro.seq8k")
    driver = harness.load_module(os.path.join(ROOT, "chipbench/drivers/train_hybrid.py"),
                                 "drv_train_hybrid")
    cfg, tc = cell.config, cell.config["train"]
    for i in range(args.seeds):
        seed = args.first_seed + i
        run = harness.Run(trace=False, seed=seed)
        job = driver.Job(cell, run, None)
        t0 = time.time()
        try:
            job.setup()
            prog = job.prog
            tps = job.tokens_per_shard
        finally:
            job.close()
            run.close()
        batches = traffic.first_batches(seed, n=cell.workload["check_steps"], batch=tc["batch"],
                                        seq=tc["seq"], tokens_per_shard=tps,
                                        vocab_size=cfg["vocab_size"])
        f32 = ref.readings(cfg, seed, batches)
        print(json.dumps({"seed": seed, "who": "program", **ref.gaps(prog, f32),
                          "losses": prog["losses"], "ref_losses": f32["losses"],
                          "s": time.time() - t0}), flush=True)
        _dump(args.dump, seed, "reference", f32)
        _dump(args.dump, seed, "program", prog)
        upper = []
        if i < args.controls:
            upper.append(("control_fp8", {"mode": "fp8"}))
        if i < args.faults:
            upper.append(("fault_half_batch", {"batch_rows": slice(0, tc["batch"] // 2)}))
        for who, kw in upper:
            t0 = time.time()
            r = ref.readings(cfg, seed, batches, **kw)
            print(json.dumps({"seed": seed, "who": who, **ref.gaps(r, f32),
                              "losses": r["losses"], "s": time.time() - t0}), flush=True)
            _dump(args.dump, seed, who, r)


def _dump(where, seed: int, who: str, reading: dict) -> None:
    if where:
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(where, f"{seed}_{who}.json"), "w") as f:
            json.dump(reading, f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--dump", default="", help="directory for the per-leaf readings")
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args()
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate_hybrid: needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup_compile_cache()
    calibrate(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
