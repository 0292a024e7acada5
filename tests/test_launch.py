"""The launch layer's chip contract on the CPU: ``chip_smoke.py`` refuses
a machine without a TPU, its phases pass at a reduced size, and the
persistent compilation cache has exactly one directory."""

from __future__ import annotations

import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import setup_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"), REPO]))
    env.update(kw)
    return env


def test_chip_smoke_refuses_cpu(tmp_path):
    r = subprocess.run([sys.executable, SMOKE], env=_env(), cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_phases_at_reduced_size(tmp_path):
    """Both phases and the bit-exact restore, through the smoke's own
    checks, at ``--reduce`` width on the CPU; compiled programs land in
    the cache directory the environment names."""
    cache = tmp_path / "jax_cache"
    code = (
        "import chip_smoke; chip_smoke.smoke(['--arch', 'granite-3-2b', '--reduce', "
        "'--n-layers', '2', '--batch', '2', '--seq', '32', '--n-shards', '2'], 3, 2)"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=tmp_path, capture_output=True, text=True,
        timeout=600,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache), TMPDIR=str(tmp_path),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[smoke] train: steps 0->3" in r.stdout
    assert "[smoke] resume: steps 3->5" in r.stdout
    assert "[smoke] restore: step 3 restored bit-exactly" in r.stdout
    assert any(n.startswith("jit_train_step") for n in os.listdir(cache))
    assert not [n for n in os.listdir(tmp_path) if n.startswith("chip_smoke_")]


def test_compile_cache_has_one_directory(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/named/by/env")
        assert setup_compile_cache() == "/named/by/env"
        assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env itself

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert setup_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().splitlines()
