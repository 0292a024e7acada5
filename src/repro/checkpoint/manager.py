"""Burst-buffer checkpointing through Sea — the paper's pattern applied to
training state.

Saves land on the *fastest tier with space* (host tmpfs — the burst
buffer) and the Sea flush daemon materializes the checkpoint to the
persistent tier asynchronously (MOVE mode: flush + evict, keeping the
burst buffer free for the next save). This is exactly the checkpoint
workflow that motivated HPC burst buffers (paper §2.1) and Sea's
copy/move semantics (§3.3).

Async saves (``save(..., async_=True)``) cost the step loop only the
device->host snapshot: a ``SaveHandle`` future returns immediately while
a coordinator thread fans the per-leaf .npy streams through the shared
TransferEngine worker pool (at most ``checkpoint_workers`` in flight),
then commits the manifest and finally the ``_COMPLETE`` marker. Saves
are serialized: a new ``save`` first waits for (and surfaces the failure
of) the previous in-flight one. On multi-host meshes each process writes
only its addressable ``replica_id == 0`` shards; manifest, marker and GC
belong to process 0.

Crash safety: the ``_COMPLETE`` marker is committed strictly after every
leaf file and the manifest; restore only considers steps whose marker
AND manifest files verify (crc32). A crash anywhere before the marker
leaves no restorable partial — the un-markered directory is reaped by
the next save's GC. ``restore_latest`` reads through the hierarchy, so a
checkpoint still sitting in the burst buffer restores at tmpfs speed —
node-local restart after preemption costs seconds, not a PFS read.

Elastic restore: pass ``shardings`` built from a *different* mesh and the
leaves are device_put against it (tests/test_checkpoint.py exercises a
reshard).
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

import jax

from repro.checkpoint import serialization as ser
from repro.core import Sea

_STEP_RE = re.compile(r"^step_(\d+)$")
_MARKER = "_COMPLETE"

log = logging.getLogger("repro.checkpoint")


class SaveHandle:
    """Future for an in-flight checkpoint save. ``result()`` blocks until
    the background writer committed the ``_COMPLETE`` marker (returning
    the step directory) or re-raises its failure."""

    def __init__(self, step: int, directory: str):
        self.step = step
        self.directory = directory
        self._done = threading.Event()
        self._exc: BaseException | None = None
        self._waiters = 0
        self._consumed = False  # outcome observed via result()
        self._lock = threading.Lock()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> str:
        with self._lock:
            self._waiters += 1
        try:
            if not self._done.wait(timeout):
                raise TimeoutError(
                    f"checkpoint save of step {self.step} still in flight"
                )
        finally:
            with self._lock:
                self._waiters -= 1
        with self._lock:
            self._consumed = True
        if self._exc is not None:
            raise self._exc
        return self.directory

    def _finish(self, exc: BaseException | None) -> bool:
        """Mark complete; True when nobody sat blocked in ``result()``
        (the write was fully hidden behind compute)."""
        self._exc = exc
        with self._lock:
            overlapped = self._waiters == 0
            if not overlapped:
                # a blocked result() caller is about to observe (and for a
                # failure, re-raise) this outcome: mark it consumed BEFORE
                # releasing the waiter, so _unsettled() can never pop the
                # handle in the window before the waiter returns and
                # re-surface the same failure to a later save()/wait()
                self._consumed = True
        self._done.set()
        return overlapped


@dataclass
class CheckpointManager:
    sea: Sea
    subdir: str = "checkpoints"
    keep_n: int = 3
    #: test/bench hook: substitute for ``sea.fs.open`` on every
    #: checkpoint byte (fault injection, modelled tier pacing)
    open_fn: Callable | None = None
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False
    )
    _inflight: SaveHandle | None = field(default=None, repr=False)
    #: last background save that failed with nobody blocked in result():
    #: the next save()/wait() surfaces it instead of letting it vanish
    _failed: SaveHandle | None = field(default=None, repr=False)

    @property
    def root(self) -> str:
        return os.path.join(self.sea.fs.mount, self.subdir)

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:08d}")

    def _open(self, path: str, mode: str = "r"):
        fn = self.open_fn or self.sea.fs.open
        return fn(path, mode)

    # ------------------------------------------------------------------ save
    def save(self, step: int, state, *, async_: bool = False,
             blocking_flush: bool = False):
        """Write the state to the burst buffer; flush happens async.

        Blocking (default): returns the step directory once the marker is
        committed (leaf writes still fan across the engine pool).
        ``async_=True``: returns a :class:`SaveHandle` as soon as the
        device->host snapshot is taken; the write proceeds behind
        compute. ``blocking_flush=True`` additionally drains the flusher
        (implies a blocking save)."""
        t0 = time.monotonic()
        prev = self._unsettled()
        if prev is not None:
            prev.result()  # serialize saves; surface a failed background write
        d = self._step_dir(step)
        self._clear_partial(d)
        manifest, jobs = ser.snapshot_tree(state)  # device -> host copy
        handle = SaveHandle(step, d)
        if async_ and not blocking_flush:
            with self._lock:
                self._inflight = handle
            threading.Thread(
                target=self._write, args=(handle, d, manifest, jobs, True),
                name=f"sea-ckpt-save-{step}", daemon=True,
            ).start()
            self.sea.fs.telemetry.record_ckpt_save(time.monotonic() - t0)
            return handle
        self._write(handle, d, manifest, jobs, False)
        self.sea.fs.telemetry.record_ckpt_save(time.monotonic() - t0)
        handle.result()  # re-raise a write failure
        if blocking_flush:
            self.sea.flusher.drain()
        return d

    def wait(self) -> None:
        """Block until any in-flight async save committed (re-raising its
        failure). Call before shutdown so ``drain()`` sees every leaf."""
        h = self._unsettled()
        if h is not None:
            h.result()

    def _unsettled(self) -> SaveHandle | None:
        """The handle the caller must settle before proceeding: the save
        still in flight, or — when the background writer already finished
        AND failed AND nobody observed it — the failed handle. Without the
        second case a fast-failing async save whose thread cleared
        ``_inflight`` first would silently swallow its error."""
        with self._lock:
            prev = self._inflight
            if prev is not None:
                return prev
            prev, self._failed = self._failed, None
        if prev is not None and prev._consumed:
            prev = None  # someone already saw (and re-raised) the failure
        return prev

    def _clear_partial(self, d: str) -> None:
        """Re-saving a step must not mix old and new leaves under a stale
        marker: drop the marker first (restore ignores the dir from here
        on), then any leftover files."""
        fs = self.sea.fs
        try:
            names = fs.listdir(d)
        except FileNotFoundError:
            return
        if _MARKER in names:
            fs.remove(os.path.join(d, _MARKER))
        for name in names:
            if name != _MARKER:
                try:
                    fs.remove(os.path.join(d, name))
                except FileNotFoundError:
                    pass

    def _write(self, handle: SaveHandle, d: str, manifest: dict, jobs,
               count_overlap: bool) -> None:
        """Coordinator for one save: leaf streams fan through the engine
        pool (bounded by ``checkpoint_workers``), then manifest, then the
        marker — strictly last, so no crash window exposes a restorable
        partial."""
        fs = self.sea.fs
        exc: BaseException | None = None
        try:
            engine = getattr(fs, "transfer", None)
            workers = max(1, getattr(fs.config, "checkpoint_workers", 2))
            if engine is not None and workers > 1 and len(jobs) > 1:
                sem = threading.BoundedSemaphore(workers)
                futs = []
                for fname, arr, entry in jobs:
                    sem.acquire()
                    futs.append(
                        engine.submit(self._write_leaf, d, fname, arr,
                                      entry, sem)
                    )
                for f in futs:
                    f.result()
            else:
                for fname, arr, entry in jobs:
                    self._write_leaf(d, fname, arr, entry, None)
            if jax.process_index() == 0:
                ser.write_manifest(manifest, d, open_fn=self._open)
                with self._open(os.path.join(d, _MARKER), "w") as f:
                    f.write(json.dumps({"step": handle.step}))
                self._gc()
        except BaseException as e:  # surfaced via handle.result()
            exc = e
        overlapped = handle._finish(exc)
        with self._lock:
            if self._inflight is handle:
                self._inflight = None
            if exc is not None and not handle._consumed:
                # failed with nobody blocked in result(): park it so the
                # next save()/wait() surfaces the error. A waiter that WAS
                # blocked has _consumed set by _finish, so the failure is
                # never delivered twice.
                self._failed = handle
        if exc is None and count_overlap and overlapped:
            fs.telemetry.record_ckpt_overlap_hit()

    def _write_leaf(self, d: str, fname: str, arr, entry: dict,
                    sem: threading.Semaphore | None) -> None:
        try:
            crc, n = ser.write_leaf(
                os.path.join(d, fname), arr, open_fn=self._open
            )
            entry["crc32"], entry["bytes"] = crc, n
            self.sea.fs.telemetry.record_ckpt_save(0.0, nbytes=n)
        finally:
            if sem is not None:
                sem.release()

    # ------------------------------------------------------------------ list
    def available_steps(self) -> list[int]:
        fs = self.sea.fs
        try:
            names = fs.listdir(self.root)
        except FileNotFoundError:
            return []
        steps = []
        for n in names:
            m = _STEP_RE.match(n)
            if not m:
                continue
            if fs.exists(os.path.join(self.root, n, _MARKER)):
                steps.append(int(m.group(1)))
        return sorted(steps)

    # ------------------------------------------------------------------ load
    def restore(self, step: int, template, shardings=None):
        d = self._step_dir(step)
        fs = self.sea.fs
        return ser.load_tree(
            template, d, open_fn=self._open, shardings=shardings,
            pool=getattr(fs, "transfer", None),
        )

    def restore_latest(self, template, shardings=None):
        """Returns (step, state) or (None, None) if nothing checkpointed.
        Corrupt/partial steps are discarded loudly: counted in telemetry
        (``ckpt_restore_fallbacks``) and logged, so a flaky tier shows up
        as itself rather than as silent slowness."""
        for step in reversed(self.available_steps()):
            try:
                return step, self.restore(step, template, shardings)
            except (IOError, ValueError, FileNotFoundError, KeyError) as e:
                self.sea.fs.telemetry.record_ckpt_restore_fallback()
                log.warning(
                    "discarding checkpoint step %d (%s: %s); "
                    "falling back to an older step",
                    step, type(e).__name__, e,
                )
                continue
        return None, None

    # ------------------------------------------------------------------ gc
    def _gc(self) -> None:
        """Prune beyond ``keep_n`` AND reap crashed partials. The seed
        leaked both ways: un-markered step dirs are invisible to
        ``available_steps`` so they were never cleaned, and pruned steps
        left their empty ``step_XXXXXXXX`` directory behind."""
        if jax.process_index() != 0:
            return
        fs = self.sea.fs
        try:
            names = fs.listdir(self.root)
        except FileNotFoundError:
            return
        complete: list[int] = []
        partial: list[int] = []
        for n in names:
            m = _STEP_RE.match(n)
            if not m:
                continue
            s = int(m.group(1))
            if fs.exists(os.path.join(self.root, n, _MARKER)):
                complete.append(s)
            else:
                partial.append(s)
        complete.sort()
        doomed = partial + complete[: max(len(complete) - self.keep_n, 0)]
        for s in doomed:
            d = self._step_dir(s)
            try:
                for name in fs.listdir(d):
                    try:
                        fs.remove(os.path.join(d, name))
                    except FileNotFoundError:
                        pass
            except FileNotFoundError:
                pass
            try:
                fs.rmdir(d)
            except OSError:
                pass  # a straggler write raced in; next GC retries


def checkpoint_sea_config(workdir: str, **kw):
    """A SeaConfig preset for checkpointing: checkpoint files are MOVEd
    (flush + evict) to the persistent tier; heartbeats stay cache-only."""
    import dataclasses

    from repro.core import default_local_config

    cfg = default_local_config(workdir, **kw)
    return dataclasses.replace(
        cfg,
        flushlist=("checkpoints/*/*",),
        evictlist=("checkpoints/*/*", "heartbeats/*"),
    )
