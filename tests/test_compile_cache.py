"""The persistent compilation cache goes where `JAX_COMPILATION_CACHE_DIR`
says, and otherwise to the fixed in-checkout `.jax_cache` directory.

Each case runs in a fresh CPU-only interpreter: the cache directory is
process-wide JAX state that a test worker must not keep.
"""

import os
import subprocess
import sys

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, REPO_ROOT

_PROBE = """
import sys
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
import jax, jax.numpy as jnp
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _probe(env_dir: str | None, action: str) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO_ROOT, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE, action], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_dir_takes_the_cache(tmp_path):
    cache = tmp_path / "cc"
    assert _probe(str(cache), "compile") == [str(cache), str(cache)]
    assert any(p.name.startswith("jit_") for p in cache.iterdir())


def test_fixed_in_checkout_dir_without_env():
    assert DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    assert _probe(None, "none") == [DEFAULT_CACHE_DIR, DEFAULT_CACHE_DIR]
