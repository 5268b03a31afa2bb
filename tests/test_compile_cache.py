"""Where the persistent compilation cache goes (``repro.compile_cache``):
to ``JAX_COMPILATION_CACHE_DIR`` when it is set, and nowhere else; to one
fixed, git-ignored path inside the checkout when it is not."""
import os
import subprocess
import sys
from pathlib import Path

from repro.compile_cache import (CACHE_ENV, DEFAULT_CACHE_DIR,
                                 compile_cache_dir)

REPO = Path(__file__).resolve().parents[1]

COMPILE = """
from repro.compile_cache import enable_compile_cache
import jax, jax.numpy as jnp
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(code, **env_over):
    env = {k: v for k, v in os.environ.items() if k != CACHE_ENV}
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.split()


def _listing(path: Path):
    return sorted(os.listdir(path)) if path.exists() else []


def test_cache_dir_is_the_env_var_or_one_fixed_ignored_path():
    assert compile_cache_dir({CACHE_ENV: "/elsewhere"}) == "/elsewhere"
    assert compile_cache_dir({}) == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_cache_written_only_where_the_env_var_says(tmp_path):
    cache = tmp_path / "cc"
    before = _listing(DEFAULT_CACHE_DIR)
    out = _run(COMPILE, **{CACHE_ENV: str(cache),
                           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert out[:2] == [str(cache), str(cache)]
    assert any(name.startswith("jit_") for name in _listing(cache))
    assert _listing(DEFAULT_CACHE_DIR) == before


def test_without_the_env_var_the_cache_is_the_fixed_path():
    out = _run(COMPILE.split("jax.jit(")[0])
    assert out == [str(DEFAULT_CACHE_DIR), str(DEFAULT_CACHE_DIR)]
