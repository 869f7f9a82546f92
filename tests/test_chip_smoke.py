"""CPU tests of the pieces around the chip bring-up check that need no chip:
the compile-cache helper, and `chip_smoke.py` refusing to run without a TPU
or without the program beside it."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, configure_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = configure_compile_cache()
        assert got == str(REPO / ".jax_cache") == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert configure_compile_cache() == got  # the same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _run_smoke(script, cwd, env):
    return subprocess.run(
        [sys.executable, str(script)], cwd=str(cwd), env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run_smoke(REPO / "chip_smoke.py", tmp_path, env)
    assert r.returncode != 0
    assert r.stdout == ""  # no phase ran, no result line
    assert "no TPU" in r.stderr


def test_chip_smoke_needs_the_program(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run_smoke(tmp_path / "chip_smoke.py", tmp_path, env)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "not beside this script" in r.stderr
