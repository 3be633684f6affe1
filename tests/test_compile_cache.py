"""Where the persistent compilation cache goes (launch/compile_cache.py).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX configuration, and these tests must not leave it set in the worker.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROG = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(16)).block_until_ready()
"""


def _run(env_extra, mode):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", _PROG, mode], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_dir_is_left_to_jax_and_written_there(tmp_path):
    cache = tmp_path / "cache"
    default = os.path.join(ROOT, ".jax_cache")
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    got = _run({"JAX_COMPILATION_CACHE_DIR": str(cache),
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
               "compile")
    assert got == [str(cache), str(cache)]
    assert any(name.startswith("jit_") for name in os.listdir(cache))
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert after == before


def test_unset_env_uses_the_fixed_repo_dir():
    want = os.path.join(ROOT, ".jax_cache")
    assert _run({}, "config") == [want, want]
