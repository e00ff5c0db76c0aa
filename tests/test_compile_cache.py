"""The persistent compilation cache helper: the environment's directory when
``JAX_COMPILATION_CACHE_DIR`` is set (written there and nowhere else),
otherwise one fixed path inside the checkout, identical across calls and
processes."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache as CC

_REPO_ROOT = Path(__file__).resolve().parents[1]


def _child(code: str, env: dict) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


def _env_without_cache_dir() -> dict:
    env = {k: v for k, v in os.environ.items() if k != CC.ENV_VAR}
    env["PYTHONPATH"] = str(_REPO_ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_default_dir_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    first, second = CC.compile_cache_dir(), CC.compile_cache_dir()
    assert first == second == str(_REPO_ROOT / ".jax_cache")
    code = ("from repro.launch.compile_cache import compile_cache_dir; "
            "print(compile_cache_dir())")
    assert _child(code, _env_without_cache_dir()) == first


def test_env_dir_honoured_and_nothing_overridden(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.compile_cache_dir() == str(tmp_path)
    assert CC.enable_compile_cache() == str(tmp_path)
    # the helper sets no directory of its own when the environment names one
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_written_only_to_env_dir(tmp_path):
    """A child compiles with the cache on: entries land in the directory
    the environment names, and the in-checkout default is not created."""
    cache = tmp_path / "cc"
    env = _env_without_cache_dir()
    env[CC.ENV_VAR] = str(cache)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    default = _REPO_ROOT / ".jax_cache"
    had_default = default.exists()
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "d = enable_compile_cache()\n"
            "jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0))"
            ".block_until_ready()\n"
            "print(d, jax.config.jax_compilation_cache_dir)")
    out = _child(code, env)
    assert out == f"{cache} {cache}"
    assert cache.is_dir() and any(cache.iterdir())
    assert default.exists() == had_default
