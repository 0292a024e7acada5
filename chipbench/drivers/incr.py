"""Driver ``incr``: the paper's incrementation application (arXiv:2207.01737,
Alg. 1) as an unmodified numpy program under ``SeaMount``, with the
increment on the device.

``workers`` threads of this one process (the process that holds the chip)
each work a chain of blocks: worker ``w`` takes blocks ``w``,
``w + workers``, ... Iteration ``i`` of block ``b`` reads
``input_{b mod input_blocks}`` (i = 1) or the block's iteration ``i - 1``
with ``np.load``, puts it on the device, adds 1 there in float32, brings
it back, writes iteration ``i`` with ``np.save`` and ``fsync``, and
removes iteration ``i - 1``. Iteration ``iterations`` is the final one:
Sea's flusher MOVEs it to the base tier.

Set-up draws the input blocks from the seed onto the base tier and runs
one block-iteration per worker: worker ``w``'s first block starts at
iteration ``1 + (iterations * w) // workers``, its first call adding that
many, so that finals fall evenly over any window. The window then runs
for ``seconds``; it counts every block-iteration that ends inside it and
the share of each one that straddles its end.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time

import numpy as np

from chipbench import traffic
from chipbench.harness import Check, WindowResult

MiB = 1 << 20


def control_add(x, c):
    """The control's device step: the add in bfloat16, each operand and the
    sum rounded explicitly (XLA may drop a float32 -> bfloat16 -> float32
    round trip as excess precision)."""
    from jax import lax

    def bf16(v):
        return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    return bf16(bf16(x) + bf16(c))


class Job:
    def __init__(self, cell, run, devices):
        self.cell, self.run, self.devices = cell, run, devices
        self.cfg, self.wl = cell.config, cell.workload
        self.n_elems = int(self.cfg["block_elems"])
        self.iters = int(self.cfg["iterations"])
        self.workers = int(self.wl["workers"])
        self.n_inputs = int(self.wl["input_blocks"])
        self.sea = self.mount = None
        self.start = threading.Event()
        self.stop = threading.Event()
        self.threads: list[threading.Thread] = []
        self.errors: list[BaseException] = []
        self.done: list[tuple[float, float]] = []   # (start, end) of window iterations
        self.chains: dict[int, dict] = {}           # worker -> {block, it, path}
        self.finals: list[tuple[int, str]] = []     # (block, path)
        self._lock = threading.Lock()

    # ------------------------------------------------------------ set-up
    def make_inc(self):
        """The device step: add ``c`` in float32."""
        import jax

        return jax.jit(lambda x, c: x + c)

    def _path(self, block: int, it: int) -> str:
        return os.path.join(self.sea.fs.mount, f"blk{block:06d}_it{it:02d}.npy")

    def setup(self) -> None:
        from repro.core import Sea, SeaMount, default_local_config

        run, cfg = self.run, self.cfg
        scfg = default_local_config(run.workdir, max_file_size=int(cfg["block_bytes"]) + (1 << 12),
                                    n_procs=self.workers)
        final = f"*_it{self.iters:02d}.npy"
        scfg = dataclasses.replace(run.own_tiers(scfg), flushlist=(final,), evictlist=(final,))
        self.sea = Sea(scfg).start()
        base = scfg.tiers[-1].roots[0]
        os.makedirs(base, exist_ok=True)
        for k in range(self.n_inputs):   # the inputs already sit on the base tier
            np.save(os.path.join(base, f"input_{k}.npy"),
                    traffic.incr_input(run.seed, k, self.n_elems))
        self.inc = self.make_inc()
        self.mount = SeaMount(self.sea.fs).__enter__()
        ready = threading.Barrier(self.workers + 1)
        for w in range(self.workers):
            t = threading.Thread(target=self._worker, args=(w, ready), name=f"incr-{w}",
                                 daemon=True)
            t.start()
            self.threads.append(t)
        ready.wait()
        if self.errors:
            raise RuntimeError("incrementation warm-up failed") from self.errors[0]

    # ------------------------------------------------------------ workflow
    def _iteration(self, block: int, it: int, add: float) -> str:
        import jax

        run = self.run
        src = (os.path.join(self.sea.fs.mount, f"input_{block % self.n_inputs}.npy")
               if it == 1 or add != 1.0 else self._path(block, it - 1))
        dst = self._path(block, it)
        with run.span("incr.read"):
            arr = np.load(src)
        with run.span("incr.h2d"):
            x = jax.device_put(arr, self.devices[0])
            x.block_until_ready()
        with run.span("incr.add"):
            y = self.inc(x, np.float32(add))
            y.block_until_ready()
        with run.span("incr.d2h"):
            out = np.asarray(y)
        del x, y, arr
        with run.span("incr.write"):
            with open(dst, "wb") as f:
                np.save(f, out)
                f.flush()
                os.fsync(f.fileno())
        if src != dst and not os.path.basename(src).startswith("input_"):
            with run.span("incr.remove"):
                os.remove(src)
        return dst

    def _worker(self, w: int, ready: threading.Barrier) -> None:
        block = w
        first = 1 + (self.iters * w) // self.workers
        try:
            path = self._iteration(block, first, float(first))
            self._note(w, block, first, path)
        except BaseException as e:  # surfaced by setup()
            self.errors.append(e)
            ready.wait()
            return
        ready.wait()
        self.start.wait()
        it = first
        try:
            while not self.stop.is_set():
                it += 1
                if it > self.iters:
                    block, it = block + self.workers, 1
                t0 = time.perf_counter()
                path = self._iteration(block, it, 1.0)
                t1 = time.perf_counter()
                with self._lock:
                    self.done.append((t0, t1))
                self._note(w, block, it, path)
        except BaseException as e:  # counted as a failed operation
            with self._lock:
                self.errors.append(e)

    def _note(self, w: int, block: int, it: int, path: str) -> None:
        with self._lock:
            self.chains[w] = {"block": block, "it": it, "path": path}
            if it == self.iters:
                self.finals.append((block, path))

    # ------------------------------------------------------------ window
    def _tier_bytes(self) -> dict:
        t = self.sea.fs.telemetry.snapshot()["tiers"]
        return {f"bytes_written.{k}": v["bytes_written"] for k, v in t.items()}

    def window(self, seconds: float) -> WindowResult:
        tel0 = self._tier_bytes()
        t0 = time.perf_counter()
        self.start.set()
        time.sleep(seconds)
        self.stop.set()
        t1 = time.perf_counter()
        for t in self.threads:
            t.join()
        tel1 = self._tier_bytes()
        # every block-iteration began inside the window; one that ends after
        # it counts by the share of its time that fell inside
        work = sum((min(e, t1) - s) / (e - s) for s, e in self.done if s < t1)
        mib = work * self.cfg["block_bytes"] / MiB
        return WindowResult(
            seconds=t1 - t0, attempted=len(self.done) + len(self.errors),
            failed=len(self.errors),
            metrics={"incr_MiB_per_s": mib / (t1 - t0)},
            counters={k: tel1.get(k, 0) - tel0.get(k, 0) for k in tel1},
            info={"block_iterations": work, "finals": len(self.finals)},
        )

    # ------------------------------------------------------------ check
    def check(self) -> list[Check]:
        """Every final and every chain's last iteration, read back through
        Sea, against its input plus its iteration count in numpy."""
        self._unmount()
        self.sea.flusher.drain()
        answers = sorted({(c["block"], c["it"], c["path"]) for c in self.chains.values()}
                         | {(b, self.iters, p) for b, p in self.finals})
        bad = 0
        by_input: dict[int, list] = {}
        for block, it, path in answers:
            by_input.setdefault(block % self.n_inputs, []).append((it, path))
        for k, items in sorted(by_input.items()):
            base = traffic.incr_input(self.run.seed, k, self.n_elems)
            for it, path in items:
                try:
                    with self.sea.fs.open(path, "rb") as f:
                        got = np.load(f)
                except (OSError, ValueError):
                    bad += 1
                    continue
                want = base + np.float32(it)
                if got.dtype != want.dtype or not np.array_equal(got, want):
                    bad += 1
        return [Check("final_bad_blocks", bad + len(self.errors),
                      self.cfg["limits"]["final_bad_blocks"])]

    def _unmount(self) -> None:
        if self.mount is not None:
            mount, self.mount = self.mount, None
            mount.__exit__(None, None, None)

    def close(self) -> None:
        self.start.set()
        self.stop.set()
        for t in self.threads:
            t.join()
        self._unmount()
        if self.sea is not None:
            sea, self.sea = self.sea, None
            sea.shutdown()
