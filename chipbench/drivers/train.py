"""Driver ``train``: the Sea-backed training job of ``repro.launch.train``.

Set-up builds one object, the jitted donated step with its state, from
the seed (the benchmark's own weights, in the configuration's dtypes),
writes the corpus through Sea and drives the first ``check_steps`` steps
through the window's own loop body and device feed. Their readings are
what the reference checks. With ``ckpt_every`` > 0 set-up ends with one
async save, and the window runs whole cycles of ``ckpt_every`` steps and
a save: it starts when that save returns and ends when the first save
that returns after ``seconds`` returns. With ``ckpt_every`` 0 the window
runs steps until ``seconds`` have passed.

The loop body makes the calls ``launch/train.py`` makes: ``next`` on the
device feed, the step, ``block_until_ready``, ``float(loss)``, the
heartbeat and, when due, ``CheckpointManager.save(..., async_=True)``.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from chipbench import flops, traffic
from chipbench.harness import Check, WindowResult
from chipbench.reference import granite as ref


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


class Job:
    def __init__(self, cell, run, devices):
        self.cell, self.run, self.devices = cell, run, devices
        self.cfg, self.wl = cell.config, cell.workload
        self.tc = self.cfg["train"]
        self.ckpt_every = int(self.wl["ckpt_every"])
        self.sea = self.pipe = self.it = self.state = self.ckpt = None
        self.handles: list = []
        self.step_no = 0
        self.prog: dict = {}

    # ------------------------------------------------------------ set-up
    def make_step(self, train_step):
        """The timed step: the program's, jitted with its state donated."""
        import jax

        return jax.jit(train_step, donate_argnums=0)

    def _argv(self) -> list[str]:
        prog, tc = self.cfg["program"], self.tc
        argv = ["--arch", prog["arch"], "--n-layers", str(self.cfg["num_hidden_layers"]),
                "--batch", str(tc["batch"]), "--seq", str(tc["seq"]),
                "--steps", str(tc["steps"]), "--lr", repr(tc["lr"]),
                "--ckpt-every", str(self.ckpt_every or tc["steps"]),
                "--workdir", self.run.workdir, "--quiet"]
        return argv + (["--reduce"] if prog.get("reduce") else [])

    def _check_program(self, mcfg, tcfg, template) -> None:
        """The program must run the configuration as the file states it."""
        import jax

        c, a, adam = self.cfg, mcfg.attention, tcfg.optimizer.adamw
        stated = {
            "hidden_size": mcfg.d_model, "intermediate_size": mcfg.d_ff,
            "num_hidden_layers": mcfg.n_layers, "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "vocab_size": mcfg.vocab_size, "rope_theta": a.rope_theta,
            "rms_norm_eps": mcfg.norm_eps, "tie_word_embeddings": mcfg.tie_embeddings,
            "hidden_act": mcfg.act,
        }
        train = {
            "param_dtype": mcfg.param_dtype, "opt_state_dtype": mcfg.opt_state_dtype,
            "remat": mcfg.remat, "b1": adam.b1, "b2": adam.b2, "eps": adam.eps,
            "weight_decay": adam.weight_decay, "max_grad_norm": adam.max_grad_norm,
            "warmup_steps": adam.schedule.warmup_steps, "steps": adam.schedule.decay_steps,
            "min_lr_ratio": adam.schedule.min_lr_ratio, "lr": adam.schedule.base_lr,
            "seq_chunk_loss": tcfg.seq_chunk_loss,
        }
        bad = {k: (c[k], v) for k, v in stated.items() if c[k] != v}
        bad.update({k: (self.tc[k], v) for k, v in train.items() if self.tc[k] != v})
        got = {_path_str(p): (tuple(x.shape), str(x.dtype))
               for p, x in jax.tree_util.tree_flatten_with_path(template["params"])[0]}
        want = {k: (s[0], s[1]) for k, s in ref.param_specs(self.cfg).items()}
        if got != want:
            bad["params"] = (sorted(set(want.items()) ^ set(got.items())), "differ")
        if bad:
            raise ValueError(f"the program departs from the configuration: {bad}")

    def _sea_config(self, largest: int):
        from repro.checkpoint.manager import checkpoint_sea_config

        return self.run.own_tiers(checkpoint_sea_config(
            self.run.workdir, max_file_size=largest + (1 << 12),
            n_procs=self.cfg["sea"]["n_procs"]))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.checkpoint.manager import CheckpointManager
        from repro.core import Sea
        from repro.data.pipeline import DataPipeline
        from repro.distributed.fault import HeartbeatMonitor
        from repro.launch.train import build_model_config, parse_args, train_config
        from repro.training.train_step import make_train_step

        tc, run = self.tc, self.run
        args = parse_args(self._argv())
        mcfg = build_model_config(args)
        tcfg = train_config(args, mcfg)
        init_state, train_step, _ = make_train_step(mcfg, tcfg)
        template = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
        self._check_program(mcfg, tcfg, template)
        self.step_fn = self.make_step(train_step)

        specs = ref.param_specs(self.cfg)
        treedef = jax.tree_util.tree_structure(template["params"])
        paths = [_path_str(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(template["params"])[0]]
        self.paths, self.specs = paths, specs

        def build(key):
            state = init_state(key)
            flat = ref.make_params(key, specs)
            state["params"] = jax.tree_util.tree_unflatten(treedef, [flat[p] for p in paths])
            return state

        def flat(tree):
            return dict(zip(paths, jax.tree_util.tree_leaves(tree), strict=True))

        grad_norms = jax.jit(lambda m: ref.leaf_norms(flat(m)))
        change = jax.jit(lambda p, key: ref.change_norms(flat(p), key, specs))

        self.tokens_per_shard = tc["batch"] * (tc["seq"] + 1) * 16
        largest = max(max(x.size * x.dtype.itemsize for x in jax.tree.leaves(template)),
                      self.tokens_per_shard * 4)
        self.sea = Sea(self._sea_config(largest)).start()
        traffic.write_corpus(self.sea, "corpus", run.seed, n_shards=self.wl["n_shards"],
                             tokens_per_shard=self.tokens_per_shard,
                             vocab_size=self.cfg["vocab_size"])
        self.ckpt = CheckpointManager(self.sea, keep_n=self.cfg["sea"]["keep_n"])
        self.hb = HeartbeatMonitor(os.path.join(self.sea.fs.mount, "heartbeats"), 0,
                                   fs=self.sea.fs)
        self.key = ref.seed_key(run.seed)
        self.state = jax.jit(build)(self.key)
        self.pipe = DataPipeline(self.sea, "corpus", batch_size=tc["batch"],
                                 seq_len=tc["seq"], start_shard=0)
        self.it = self.pipe.device_iter()

        losses = []
        b1 = tc["b1"]
        for i in range(self.wl["check_steps"]):
            losses.append(self._step())
            if i == 0:
                g = grad_norms(self.state["opt"]["m"])
                self.prog["grad"] = {k: float(v) / (1 - b1) for k, v in g.items()}
        self.prog["losses"] = losses
        self.prog["change"] = {k: float(v) for k, v in
                               change(self.state["params"], self.key).items()}
        if self.ckpt_every:
            self._save()
        self.flops_per_step = (flops.train_flops_per_token(self.cfg, tc["seq"])
                               * tc["batch"] * tc["seq"])

    # ------------------------------------------------------------ loop body
    def _step(self) -> float:
        import jax

        run = self.run
        with run.span("feed.next"):
            batch = next(self.it)
        with run.span("step"):
            self.state, metrics = self.step_fn(self.state, batch)
            jax.block_until_ready(self.state)
        loss = float(metrics["loss"])
        with run.span("hb.beat"):
            self.hb.beat(self.step_no)
        self.step_no += 1
        return loss

    def _save(self) -> None:
        with self.run.span("ckpt.save"):
            self.handles.append(self.ckpt.save(self.step_no, self.state, async_=True))

    def _telemetry(self) -> dict:
        t = self.sea.fs.telemetry.snapshot()
        return {"device_feed_stalls": t["device_feed_stalls"],
                "ckpt_overlap_hits": t["ckpt_overlap_hits"]}

    def window(self, seconds: float) -> WindowResult:
        tel0 = self._telemetry()
        saved_before = len(self.handles)
        steps = 0
        t0 = time.perf_counter()
        if self.ckpt_every:
            while True:
                for _ in range(self.ckpt_every):
                    self._step()
                    steps += 1
                self._save()
                if time.perf_counter() - t0 >= seconds:
                    break
        else:
            while time.perf_counter() - t0 < seconds:
                self._step()
                steps += 1
        t1 = time.perf_counter()
        tel1 = self._telemetry()
        # saves that settled inside the window: the one set-up made and
        # every window save but the last (each save first waits for the one
        # before it)
        settled = sum(h.done() for h in self.handles)
        tokens = steps * self.tc["batch"] * self.tc["seq"]
        return WindowResult(
            seconds=t1 - t0, attempted=steps, failed=0,
            metrics={"train_tokens_per_s": tokens / (t1 - t0)},
            counters={k: tel1[k] - tel0[k] for k in tel1},
            info={"steps": steps, "saves": len(self.handles) - saved_before,
                  "settled_saves": settled, "flops_per_step": self.flops_per_step},
        )

    # ------------------------------------------------------------ check
    def _ckpt_bad_leaves(self) -> int:
        """Leaves of the window's last save whose committed bytes, read back
        through Sea, differ from the live state they were taken from (no
        step ran after that save)."""
        import jax

        self.ckpt.wait()
        d = os.path.join(self.ckpt.root, f"step_{self.step_no:08d}")
        bad = 0
        for i, leaf in enumerate(jax.tree_util.tree_leaves(self.state)):
            want = np.asarray(leaf)
            try:
                with self.sea.fs.open(os.path.join(d, f"{i:05d}.npy"), "rb") as f:
                    got = np.load(f, allow_pickle=False)
            except (OSError, ValueError):
                bad += 1
                continue
            if got.shape != want.shape or got.tobytes() != want.tobytes():
                bad += 1
        return bad

    def _release(self) -> None:
        """Free the program's state, stop its threads, final flush."""
        self.state = None
        if self.it is not None:
            self.it.close()
            self.it = None
        if self.pipe is not None:
            self.pipe.close()
            self.pipe = None
        if self.sea is not None:
            try:
                if self.ckpt is not None:
                    self.ckpt.wait()
            finally:
                sea, self.sea = self.sea, None
                sea.shutdown()

    def check(self) -> list[Check]:
        limits = self.cfg["limits"]
        checks = []
        t0 = time.monotonic()
        if self.ckpt_every:
            checks.append(Check("ckpt_bad_leaves", self._ckpt_bad_leaves(),
                                limits["ckpt_bad_leaves"]))
        self._release()
        t1 = time.monotonic()
        tc = self.tc
        batches = traffic.first_batches(
            self.run.seed, n=self.wl["check_steps"], batch=tc["batch"], seq=tc["seq"],
            tokens_per_shard=self.tokens_per_shard, vocab_size=self.cfg["vocab_size"])
        reading = ref.readings(self.cfg, self.run.seed, batches)
        for name, value in ref.gaps(self.prog, reading).items():
            checks.append(Check(name, value, limits[name]))
        print(f"[bench] check: read-back and release {t1 - t0:.3f} s, "
              f"reference {time.monotonic() - t1:.3f} s", file=sys.stderr, flush=True)
        return checks

    def close(self) -> None:
        self._release()
