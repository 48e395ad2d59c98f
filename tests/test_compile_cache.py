"""The entry points' persistent compilation cache location.

Each case runs in a subprocess: JAX initializes its cache once per process.
"""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

_PROBE = """
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile!r}:
    y = jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((64, 64)))
    y.block_until_ready()
"""


def _probe(env_dir, compile_something):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = _PROBE.format(src=str(ROOT / "src"), compile=compile_something)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_cache_goes_where_the_environment_says(tmp_path):
    default = ROOT / ".jax_cache"
    before = sorted(default.iterdir()) if default.exists() else None
    returned, configured = _probe(tmp_path, True)
    assert returned == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"
    after = sorted(default.iterdir()) if default.exists() else None
    assert after == before, "an entry landed in the default directory"


def test_cache_defaults_to_the_checkout_root():
    returned, configured = _probe(None, False)
    assert returned == configured == str(ROOT / ".jax_cache")
