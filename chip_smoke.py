"""Chip smoke test: the Sea-backed training path on one TPU.

    python chip_smoke.py

Runs ``repro.launch.train.main`` twice in this one process (the process
that holds the chip) on a fresh work directory, at granite-3-2b's
published widths with only the depth cut:

- ``train``: writes the synthetic corpus through Sea, takes a few steps
  and makes one async checkpoint save, which must be committed on the
  base tier after Sea's final flush;
- ``resume``: the same job with two more steps must resume from that
  checkpoint, take exactly those two steps, and discard no checkpoint.
  The checkpoint it resumed from is then restored once more and compared
  bit for bit with the files the save committed.

Every number it prints is from one run of a smoke test, not a benchmark.
It exits non-zero when JAX finds no TPU, and when any phase fails; the
last line of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

ARCH = "granite-3-2b"
# one v5e chip holds 16 GB; bf16 params with fp32 AdamW moments take
# ~0.8 GB per granite layer, so all 40 layers need ~31 GB for state alone
N_LAYERS = 8
BATCH, SEQ = 4, 1024
STEPS, RESUME_STEPS = 6, 2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's own compile-duration events (trace, lowering, backend
    compile) and counts persistent-cache hits and misses."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.cache_hits, self.cache_misses


def _tier_roots(workdir: str) -> tuple[str, str]:
    """(tmpfs tier root, base tier root) of the job's Sea hierarchy."""
    from repro.checkpoint.manager import checkpoint_sea_config

    tiers = checkpoint_sea_config(workdir).tiers
    base = next(t for t in tiers if t.persistent)
    return tiers[0].roots[0], base.roots[0]


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"smoke check failed: {what}")


def run_phase(name: str, argv: list[str], workdir: str, clock: CompileClock,
              *, expect_start: int, expect_steps: int) -> dict:
    """One call of ``train.main``; prints the phase's report lines and
    raises on any failed check."""
    import jax

    from repro.launch.train import main as train_main

    tmpfs_root, base_root = _tier_roots(workdir)
    tmpfs_mount = os.path.dirname(tmpfs_root)
    free0 = shutil.disk_usage(tmpfs_mount).free
    c0, hits0, miss0 = clock.mark()
    t0 = time.perf_counter()
    res = train_main(argv)
    wall = time.perf_counter() - t0
    c1, hits1, miss1 = clock.mark()
    free1 = shutil.disk_usage(tmpfs_mount).free
    tel = res["telemetry"]
    batch, seq = int(argv[argv.index("--batch") + 1]), int(argv[argv.index("--seq") + 1])

    _require(res["start_step"] == expect_start,
             f"{name}: started at step {res['start_step']}, expected {expect_start}")
    _require(res["steps"] == expect_steps,
             f"{name}: took {res['steps']} steps, expected {expect_steps}")
    _require(all(math.isfinite(x) for x in res["losses"]),
             f"{name}: non-finite loss in {res['losses']}")
    _require(tel["ckpt_restore_fallbacks"] == 0,
             f"{name}: {tel['ckpt_restore_fallbacks']} checkpoint(s) discarded on restore")
    last = expect_start + expect_steps
    marker = os.path.join(base_root, "checkpoints", f"step_{last:08d}", "_COMPLETE")
    _require(os.path.exists(marker), f"{name}: no _COMPLETE marker on the base tier at {marker}")

    timed = res["step_s"][1:]
    tok_s = batch * seq * len(timed) / sum(timed) if timed else float("nan")
    stats = jax.devices()[0].memory_stats() or {}
    tiers = {k: v["bytes_written"] for k, v in tel["tiers"].items()}
    log(f"{name}: steps {expect_start}->{last}, wall {wall:.3f} s, "
        f"compile {c1 - c0:.3f} s (persistent cache hits {hits1 - hits0}, "
        f"misses {miss1 - miss0})")
    log(f"{name}: step seconds {[round(s, 4) for s in res['step_s']]}; "
        f"tokens/s over steps after the first {tok_s:.1f} (block_until_ready)")
    log(f"{name}: losses {[round(x, 4) for x in res['losses']]}")
    log(f"{name}: bytes checkpointed {tel['ckpt_bytes']}, bytes written per tier {tiers}")
    log(f"{name}: peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')} "
        f"(process peak so far), bytes_limit {stats.get('bytes_limit', 'not reported')}")
    log(f"{name}: tmpfs tier free before {free0}, after {free1} ({tmpfs_mount})")
    log(f"{name}: device_feed_stalls {tel['device_feed_stalls']}, "
        f"ckpt_save_s {tel['ckpt_save_s']:.6f}, ckpt_overlap_hits {tel['ckpt_overlap_hits']}, "
        f"ckpt_restore_fallbacks {tel['ckpt_restore_fallbacks']}")
    need = res["admission_bytes"]
    if tel["breaker_opens"] or not tiers.get("tmpfs"):
        log(f"{name}: checkpoint did not land on tmpfs alone: breaker_opens "
            f"{tel['breaker_opens']} (ENOSPC relocations trip the breaker), tmpfs "
            f"free before {free0} vs admission need n_procs * max_file_size = {need}")
    else:
        log(f"{name}: checkpoint landed on tmpfs without relocation "
            f"(breaker_opens 0; admission need {need} <= free {free0})")
    return res


def check_restore_bit_exact(argv: list[str], workdir: str, step: int) -> None:
    """Restore ``step`` into a fresh template through CheckpointManager
    and compare every leaf, bit for bit, with the files the save
    committed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import serialization as ser
    from repro.checkpoint.manager import CheckpointManager, checkpoint_sea_config
    from repro.core import Sea
    from repro.launch.train import build_model_config, parse_args, train_config
    from repro.training.train_step import make_train_step

    args = parse_args(argv)
    cfg = build_model_config(args)
    init_state, _, _ = make_train_step(cfg, train_config(args, cfg))
    template = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
    sea = Sea(checkpoint_sea_config(workdir)).start()
    try:
        ckpt = CheckpointManager(sea)
        t0 = time.perf_counter()
        restored = ckpt.restore(step, template)
        jax.block_until_ready(restored)
        t_restore = time.perf_counter() - t0
        d = os.path.join(ckpt.root, f"step_{step:08d}")
        saved = ser.load_manifest(d, sea.fs.open)["leaves"]
        manifest, jobs = ser.snapshot_tree(restored)  # device -> host
        del restored
        _require(set(manifest["leaves"]) == set(saved),
                 "restore: leaves differ from the saved manifest")
        host = {fname: arr for fname, arr, _ in jobs}
        n_bytes = 0
        for key, meta in manifest["leaves"].items():
            want = ser.read_leaf(d, key, saved[key], sea.fs.open)
            got = host[meta["shards"][0]["file"]]
            _require(
                got.shape == want.shape and got.dtype.itemsize == want.dtype.itemsize
                and np.array_equal(got.reshape(-1).view(np.uint8),
                                   want.reshape(-1).view(np.uint8)),
                f"restore: leaf {key} differs from the saved bytes",
            )
            n_bytes += got.nbytes
    finally:
        sea.shutdown()
    log(f"restore: step {step} restored bit-exactly, {len(saved)} leaves, "
        f"{n_bytes} bytes, restore {t_restore:.3f} s")


def smoke(model_args: list[str], steps: int, resume_steps: int) -> None:
    """Both phases and the bit-exact restore on a fresh work directory,
    removed afterwards with the job's tmpfs tier root."""
    clock = CompileClock()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    tmpfs_root, _ = _tier_roots(workdir)
    try:
        common = [*model_args, "--workdir", workdir, "--ckpt-every", str(steps)]
        run_phase("train", [*common, "--steps", str(steps)], workdir, clock,
                  expect_start=0, expect_steps=steps)
        resume_argv = [*common, "--steps", str(steps + resume_steps)]
        run_phase("resume", resume_argv, workdir, clock,
                  expect_start=steps, expect_steps=resume_steps)
        check_restore_bit_exact(resume_argv, workdir, steps)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(tmpfs_root, ignore_errors=True)


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} devices only",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs.base import get_config
    from repro.launch.compile_cache import setup_compile_cache

    log("smoke run, not a benchmark: one run of each phase, numbers are indicative")
    log(f"device {dev.platform} {dev.device_kind}, count {len(devices)}")
    log(f"compile cache {setup_compile_cache()}")
    cfg = get_config(ARCH)
    a = cfg.attention
    log(f"model {ARCH} at its published widths (d_model {cfg.d_model}, d_ff {cfg.d_ff}, "
        f"{a.num_heads}/{a.num_kv_heads} heads x {a.head_dim}, vocab {cfg.vocab_size}); "
        f"depth cut {cfg.n_layers}->{N_LAYERS} layers because bf16 params with fp32 AdamW "
        f"moments for all {cfg.n_layers} layers need ~31 GB and one chip holds 16 GB; "
        f"batch {BATCH} x seq {SEQ}")
    smoke(["--arch", ARCH, "--n-layers", str(N_LAYERS),
           "--batch", str(BATCH), "--seq", str(SEQ)], STEPS, RESUME_STEPS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
