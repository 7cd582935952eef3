"""Where the entry points' persistent compilation cache lands.

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX configuration, and this test process must keep compiling without it.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import os, sys
import jax
import repro.core.engine, repro.models.equivariant, repro.serve  # noqa: F401
assert jax.config.jax_compilation_cache_dir == os.environ.get(
    "JAX_COMPILATION_CACHE_DIR"), "importing the library set the cache"
from repro.compile_cache import enable_compile_cache
used = enable_compile_cache()
jax.jit(lambda x: x * 2.0)(1.0).block_until_ready()
print("USED", used)
print("CONFIG", jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        # write even a millisecond compile, so the test can see the entry
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = dict(ln.split(" ", 1) for ln in out.stdout.splitlines()
                 if ln.startswith(("USED ", "CONFIG ")))
    return lines["USED"], lines["CONFIG"]


def test_cache_dir_from_environment(tmp_path):
    used, config = _probe(tmp_path)
    assert used == config == str(tmp_path)
    assert os.listdir(tmp_path), "nothing was cached in the given directory"


def test_cache_dir_defaults_inside_checkout():
    used, config = _probe(None)
    assert used == config == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
