"""End-to-end training driver: Sea-staged data -> pjit train loop ->
burst-buffer checkpoints -> crash-safe resume.

    PYTHONPATH=src python -m repro.launch.train \
        --arch granite-3-2b --reduce --steps 200 --batch 8 --seq 256 \
        --workdir /tmp/sea_run --ckpt-every 25

``--n-layers`` keeps an architecture's published widths and cuts only its
depth (``chip_smoke.py`` runs granite-3-2b at 8 of 40 layers on one chip).

The same driver powers the fault-tolerance integration test
(--simulate-failure N aborts the process mid-run; a relaunch with the
same workdir resumes from the latest complete checkpoint).
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager, checkpoint_sea_config
from repro.configs.base import get_config
from repro.core import Sea
from repro.data.pipeline import DataPipeline, write_dataset
from repro.distributed.fault import HeartbeatMonitor
from repro.launch.compile_cache import setup_compile_cache
from repro.training.optimizer import AdamWConfig, OptimizerConfig, Schedule
from repro.training.train_step import TrainConfig, make_train_step


def small_lm(n_params_m: int = 20, vocab: int = 8192):
    """A ~n_params_m-million-parameter dense LM for CPU-scale end-to-end
    runs (d_model chosen so 12·L·d² + 2·V·d ≈ target)."""
    from repro.configs.base import AttentionConfig, ModelConfig

    n_layers = 8
    target = n_params_m * 1e6
    # params ≈ n_layers * 12 d^2 + 2 V d  (SwiGLU w/ d_ff=2.67d ≈ 8d^2 + attn 4d^2)
    a, b, c = n_layers * 12, 2 * vocab, -target
    d = int((-b + math.sqrt(b * b - 4 * a * c)) / (2 * a) // 64 * 64) or 64
    return ModelConfig(
        name=f"small-{n_params_m}m",
        family="dense",
        n_layers=n_layers,
        d_model=d,
        d_ff=int(d * 8 / 3 // 64 * 64) or 128,
        vocab_size=vocab,
        pattern=("attn:mlp",),
        attention=AttentionConfig(
            num_heads=max(d // 64, 1), num_kv_heads=max(d // 128, 1),
            head_dim=64, q_chunk=128, kv_chunk=128,
        ),
        remat="none",
    )


# placement reserves max_file_size per open write; an .npy file is its
# payload plus a header that is well under this
_NPY_HEADER_ROOM = 1 << 12


def build_model_config(args):
    if args.arch == "small":
        cfg = small_lm(args.params_m)
    else:
        cfg = get_config(args.arch)
        if args.reduce:
            from repro.configs.archs import reduced

            cfg = reduced(cfg)
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    return cfg


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="small",
                    help="'small' or any assigned arch id (with --reduce)")
    ap.add_argument("--params-m", type=int, default=20)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="cut the depth to this many layers, widths kept")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--workdir", default="/tmp/sea_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none")
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="abort() at this step (fault-tolerance testing)")
    ap.add_argument("--n-shards", type=int, default=8)
    ap.add_argument("--quiet", action="store_true")
    return ap.parse_args(argv)


def train_config(args, cfg) -> TrainConfig:
    return TrainConfig(
        optimizer=OptimizerConfig(
            kind="adamw",
            adamw=AdamWConfig(
                state_dtype=cfg.opt_state_dtype,
                schedule=Schedule(base_lr=args.lr, warmup_steps=10,
                                  decay_steps=max(args.steps, 20)),
            ),
        ),
        microbatches=args.microbatches,
        compression=args.compression,
        seq_chunk_loss=min(args.seq, 512),
    )


def main(argv=None) -> dict:
    args = parse_args(argv)
    setup_compile_cache()
    cfg = build_model_config(args)
    log = (lambda *a: None) if args.quiet else (lambda *a: print(*a, flush=True))

    # ---- train step ----------------------------------------------------------
    init_state, train_step, _ = make_train_step(cfg, train_config(args, cfg))
    train_step = jax.jit(train_step, donate_argnums=0)
    template = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))

    # ---- Sea: placement admits a write where n_procs * max_file_size is
    # free, so size it for the largest file this job writes --------------
    tokens_per_shard = args.batch * (args.seq + 1) * 16
    largest = max(
        max(x.size * x.dtype.itemsize for x in jax.tree.leaves(template)),
        tokens_per_shard * 4,  # int32 dataset shard
    )
    os.makedirs(args.workdir, exist_ok=True)
    sea = Sea(checkpoint_sea_config(
        args.workdir, max_file_size=largest + _NPY_HEADER_ROOM, n_procs=2
    )).start()

    # ---- dataset (build once; later runs reuse the persistent copy) --------
    ds_meta = os.path.join(sea.fs.mount, "dataset", "corpus", "meta.json")
    if not sea.fs.exists(ds_meta):
        log(f"[data] writing {args.n_shards} shards through Sea")
        write_dataset(
            sea, "corpus",
            n_shards=args.n_shards,
            tokens_per_shard=tokens_per_shard,
            vocab_size=cfg.vocab_size,
        )

    ckpt = CheckpointManager(sea, keep_n=3)
    hb = HeartbeatMonitor(os.path.join(sea.fs.mount, "heartbeats"), 0, fs=sea.fs)

    start_step, state = ckpt.restore_latest(template)
    if state is None:
        state = jax.jit(init_state)(jax.random.PRNGKey(0))
        start_step = 0
        log(f"[init] fresh start: {cfg.name}, {cfg.n_layers} layers, "
            f"{sum(x.size for x in jax.tree.leaves(state['params'])):,} params")
    else:
        log(f"[init] resumed from checkpoint step {start_step}")

    async_ckpt = bool(getattr(sea.fs.config, "checkpoint_async", True))
    pipe = DataPipeline(
        sea, "corpus", batch_size=args.batch, seq_len=args.seq,
        start_shard=0,
    )
    it = pipe.device_iter()   # batches arrive already device_put
    losses, step_s = [], []
    t_start = time.time()
    try:
        for step in range(start_step, args.steps):
            try:
                batch = next(it)
            except StopIteration:
                pipe.close()
                pipe = DataPipeline(sea, "corpus", batch_size=args.batch,
                                    seq_len=args.seq)
                it = pipe.device_iter()
                batch = next(it)
            t0 = time.perf_counter()
            state, metrics = train_step(state, batch)
            jax.block_until_ready(state)
            step_s.append(time.perf_counter() - t0)
            loss = float(metrics["loss"])
            losses.append(loss)
            hb.beat(step)
            if not args.quiet and (step % 10 == 0 or step == args.steps - 1):
                toks = args.batch * args.seq / step_s[-1]
                log(f"[step {step:5d}] loss={loss:.4f} "
                    f"gnorm={float(metrics['grad_norm']):.2f} tok/s={toks:,.0f}")
            if args.simulate_failure and step + 1 == args.simulate_failure:
                log(f"[fault] simulating crash at step {step + 1}")
                os._exit(17)   # hard abort: no drain, no atexit
            if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
                # async: the step loop pays only the device->host snapshot;
                # leaf writes overlap the next ckpt_every steps of compute
                out = ckpt.save(step + 1, state, async_=async_ckpt)
                d = out.directory if async_ckpt else out
                log(f"[ckpt] step {step + 1} -> {d} "
                    f"({'async' if async_ckpt else 'blocking'})")
    finally:
        # error path included: never leave the staging / device-feed
        # threads reading shards after the loop is gone
        pipe.close()
    ckpt.wait()      # last async save must commit before the final drain
    sea.shutdown()   # final flush: checkpoints materialize on the PFS tier
    wall = time.time() - t_start
    result = {
        "losses": losses,
        "start_step": start_step,
        "steps": len(losses),
        "step_s": step_s,
        "wall_s": wall,
        # free bytes placement requires of a root before it admits a write
        "admission_bytes": sea.fs.config.n_procs * sea.fs.config.max_file_size,
        "telemetry": sea.fs.telemetry.snapshot(),
    }
    log(f"[done] {len(losses)} steps in {wall:.0f}s; "
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return result


if __name__ == "__main__":
    main()
