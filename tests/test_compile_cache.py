"""Where the entry points put JAX's persistent compilation cache."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def _probe(env_extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", **env_extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    out = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                         text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_cache_dir_from_environment(tmp_path):
    """$JAX_COMPILATION_CACHE_DIR, when set, is the cache: entries land
    there and nothing else is configured."""
    d = str(tmp_path / "cache")
    got, cfg = _probe({"JAX_COMPILATION_CACHE_DIR": d,
                       "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert got == cfg == d
    assert os.listdir(d)


def test_cache_dir_defaults_to_checkout():
    """Unset, the cache is the fixed, gitignored ``<repo>/.jax_cache``."""
    got, cfg = _probe({})
    assert got == cfg == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
