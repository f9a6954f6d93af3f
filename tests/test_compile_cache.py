"""Where entry points put JAX's persistent compilation cache.

Each case runs in a child process: the cache directory is process-wide
JAX config, and the test session itself must not set it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]

_CHILD = """
import json, jax, jax.numpy as jnp
from repro.runtime import compile_cache
used = compile_cache.enable()
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({{"used": used,
                  "config": jax.config.jax_compilation_cache_dir}}))
"""


def _child(env_dir, compile_: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(compile=compile_)],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_the_only_cache(tmp_path):
    got = _child(tmp_path, compile_=True)
    assert got["used"] == got["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached in the env dir"


def test_default_is_fixed_repo_dir():
    got = _child(None, compile_=False)
    want = str(REPO / ".jax_cache")
    assert got["used"] == got["config"] == want
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"


def test_default_dir_is_gitignored():
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
