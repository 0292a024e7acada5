"""CPU rehearsals of the benchmark: every driver drives a whole run at a
tiny size and comes out correct; the same run with the timed path broken
underneath comes out not correct, once for each fault a cell can have;
and the command itself refuses to run without a TPU.

Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.reference import granite as ref

import tiny  # noqa: E402  (chipbench/tests)

ROOT = harness.ROOT


def _driver(name):
    return harness.load_module(os.path.join(harness.HERE, "drivers", f"{name}.py"),
                               f"chipbench_driver_{name}")


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite-3-2b.steady",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_tpu():
    p = _run_command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_fails_in_a_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------------------------------ sound runs
@pytest.mark.parametrize("ckpt_every", [0, 5])
def test_train_cell_correct(ckpt_every):
    cell = tiny.cell("granite-3-2b.steady", tiny.train_config(), ckpt_every=ckpt_every)
    result = tiny.drive(cell, seconds=1.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
    if ckpt_every:
        assert result["checks"]["ckpt_bad_leaves"]["value"] == 0


def test_incr_cell_correct():
    result = tiny.drive(tiny.cell("incr-617MiB.inmem", tiny.incr_config()), seconds=1.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"incr_MiB_per_s", "setup_s"}


# ------------------------------------------------------------------ broken runs
def _unchanged(train_step):
    import jax

    def step(state, batch):
        _, metrics = train_step(state, batch)
        return state, metrics

    return jax.jit(step)


def _half_batch(train_step):
    import jax

    def step(state, batch):
        return train_step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

    return jax.jit(step, donate_argnums=0)


@pytest.mark.parametrize("fault,number", [(_unchanged, "change_gap"),
                                          (_half_batch, "grad_gap")])
def test_train_fault_is_not_correct(monkeypatch, fault, number):
    drv = _driver("train")
    monkeypatch.setattr(drv.Job, "make_step", lambda self, train_step: fault(train_step))
    cell = tiny.cell("granite-3-2b.steady", tiny.train_config(), ckpt_every=0)
    result = tiny.drive(cell, seconds=0.5)
    assert not result["correct"]
    c = result["checks"][number]
    assert c["value"] > c["limit"], result["checks"]


def test_train_control_is_not_correct(monkeypatch):
    """The reference in the program's place, every product in fp8."""
    drv = _driver("train")
    setup = drv.Job.setup

    def fp8_setup(self):
        setup(self)
        tc = self.tc
        from chipbench import traffic

        batches = traffic.first_batches(self.run.seed, n=self.wl["check_steps"],
                                        batch=tc["batch"], seq=tc["seq"],
                                        tokens_per_shard=self.tokens_per_shard,
                                        vocab_size=self.cfg["vocab_size"])
        self.prog = ref.readings(self.cfg, self.run.seed, batches, mode="fp8")

    monkeypatch.setattr(drv.Job, "setup", fp8_setup)
    cell = tiny.cell("granite-3-2b.steady", tiny.train_config(), ckpt_every=0)
    result = tiny.drive(cell, seconds=0.5)
    assert not result["correct"], result["checks"]


def test_checkpoint_altered_where_written_is_not_correct(monkeypatch):
    from repro.checkpoint import serialization

    write_leaf = serialization.write_leaf

    def altered(path, arr, open_fn=open):
        if arr.size > 1:
            arr = arr.copy()
            arr.reshape(-1)[0] += 1
        return write_leaf(path, arr, open_fn)

    monkeypatch.setattr(serialization, "write_leaf", altered)
    result = tiny.drive(tiny.cell("granite-3-2b.steady", tiny.train_config(), ckpt_every=5),
                        seconds=0.5)
    assert not result["correct"]
    assert result["checks"]["ckpt_bad_leaves"]["value"] > 0


@pytest.mark.parametrize("broken", [
    "control_bf16", "fault_unchanged", "fault_altered",
])
def test_incr_fault_is_not_correct(monkeypatch, broken):
    import jax

    drv = _driver("incr")
    fns = {
        "control_bf16": drv.control_add,
        "fault_unchanged": lambda x, c: x + 0 * c,
        "fault_altered": lambda x, c: (x + c).at[7].add(1.0),
    }
    monkeypatch.setattr(drv.Job, "make_inc", lambda self: jax.jit(fns[broken]))
    result = tiny.drive(tiny.cell("incr-617MiB.inmem", tiny.incr_config()), seconds=0.5)
    assert not result["correct"]
    assert result["checks"]["final_bad_blocks"]["value"] > 0


def test_run_removes_tier_roots_outside_its_workdir():
    from repro.core import default_local_config

    run = harness.Run(trace=False, seed=1)
    scfg = run.own_tiers(default_local_config(run.workdir))
    roots = [r for t in scfg.tiers for r in t.roots]
    for r in roots:
        os.makedirs(r, exist_ok=True)
    assert run.outside and all(r in roots for r in run.outside)
    assert not any(r.startswith(run.workdir) for r in run.outside)
    run.close()
    assert not any(os.path.exists(r) for r in roots + [run.workdir])
