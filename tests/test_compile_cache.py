"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache is process-wide JAX
configuration, and importing the library must leave it off.
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = """
import jax, jax.numpy as jnp
assert not jax.config.jax_compilation_cache_dir   # import enables nothing
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def run(env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        script = SCRIPT.replace("assert not", "assert")
    else:
        # report the directory without compiling into the checkout
        script = SCRIPT.rsplit("\n", 2)[0]
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_is_used_and_filled(tmp_path):
    assert run(tmp_path) == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_default_is_fixed_dir_in_checkout():
    assert run() == str(REPO / ".jax_cache")
