"""The persistent compile cache lands where JAX_COMPILATION_CACHE_DIR says,
and otherwise at ``.jax_cache`` in the checkout (repro.launch.compile_cache).
Each case runs in a fresh interpreter: the cache directory is process-wide
JAX state."""

import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
where = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if COMPILE:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(where)
print(jax.config.jax_compilation_cache_dir)
"""


def _run(env_dir, compile_: bool):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(SRC)
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_}\n" + PROBE],
        env=env, capture_output=True, text=True, timeout=300, check=True,
    )
    return out.stdout.split()


def test_env_dir_holds_the_entries(tmp_path):
    where, configured = _run(tmp_path, compile_=True)
    assert where == configured == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_default_dir_is_fixed_in_the_checkout():
    where, configured = _run(None, compile_=False)
    want = str(SRC.parent / ".jax_cache")
    assert where == configured == want
