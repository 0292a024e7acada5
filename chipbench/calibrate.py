"""Readings that the limits of ``correct`` are set from. Run on the chip.

    python3 chipbench/calibrate.py train --seeds 12 --faults 3 [--first-seed N]
    python3 chipbench/calibrate.py incr --seeds 3 --seconds 4 [--first-seed N]

``train``: in one process, for each seed, the program's readings from the
driver's own set-up (the jitted step, its state and the device feed at
the cell's size) against the float32 reference: the lower readings. For
the first ``--faults`` seeds also the control (the reference in the
program's place, every matrix product in fp8) and a planted fault (the
reference in the program's place, the mean taken over half of each
batch's rows) against the same reference: the upper readings.

``incr``: for each seed, the incrementation cell driven for ``--seconds``
with the device step broken in three ways: the control (the add in
bfloat16), a step that returns its input unchanged, and a result altered
where it is produced. Each prints its ``final_bad_blocks``.

Prints one JSON object per reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(name: str):
    from chipbench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return harness.Cell.load(bench, name)


def calibrate_train(args) -> None:
    from chipbench import harness, traffic
    from chipbench.reference import granite as ref

    cell = _cell("granite-3-2b.steady")
    driver = harness.load_module(os.path.join(ROOT, "chipbench/drivers/train.py"), "drv_train")
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
        out = {"seed": seed, "who": "program", **ref.gaps(prog, f32),
               "losses": prog["losses"], "ref_losses": f32["losses"], "s": time.time() - t0}
        print(json.dumps(out), flush=True)
        if i < args.faults:
            for who, kw in (("control_fp8", {"mode": "fp8"}),
                            ("fault_half_batch", {"batch_rows": slice(0, tc["batch"] // 2)})):
                r = ref.readings(cfg, seed, batches, **kw)
                print(json.dumps({"seed": seed, "who": who, **ref.gaps(r, f32),
                                  "losses": r["losses"]}), flush=True)


def calibrate_incr(args) -> None:
    import jax

    from chipbench import harness

    cell = _cell("incr-617MiB.inmem")
    driver = harness.load_module(os.path.join(ROOT, "chipbench/drivers/incr.py"), "drv_incr")
    breaks = {
        "control_bf16": driver.control_add,
        "fault_unchanged": lambda x, c: x + 0 * c,
        "fault_altered": lambda x, c: (x + c).at[12345].add(1.0),
    }
    for i in range(args.seeds):
        seed = args.first_seed + i
        for who, fn in breaks.items():
            if args.only and who not in args.only:
                continue
            run = harness.Run(trace=False, seed=seed)
            job = driver.Job(cell, run, jax.devices()[:1])
            job.make_inc = lambda fn=fn: jax.jit(fn)
            try:
                job.setup()
                win = job.window(args.seconds)
                checks = job.check()
            finally:
                job.close()
                run.close()
            print(json.dumps({"seed": seed, "who": who, "block_iterations": win.info,
                              **{c.name: c.value for c in checks}}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train", "incr"))
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--only", nargs="*", help="incr: the broken steps to read (default all)")
    args = ap.parse_args()
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup_compile_cache()
    (calibrate_train if args.what == "train" else calibrate_incr)(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
