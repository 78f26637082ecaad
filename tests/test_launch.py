"""Entry-point start-up: the chip smoke test's device check and the
persistent compilation cache placement."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import init_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke test exits non-zero, names the platform it
    found, and prints no result line."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr, \
        proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield prev
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert init_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing in code sets another dir
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert str(compile_cache.CHECKOUT_CACHE_DIR) == want
    assert init_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
