"""Where ``configure_compile_cache`` puts JAX's persistent cache.  Each
case runs in a fresh process: the setting is global to a process."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_CODE = """
import jax
from repro.launch.compile_cache import configure_compile_cache
before = jax.config.jax_compilation_cache_dir
print(before, configure_compile_cache(), jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_set", [False, True])
def test_configure_compile_cache_location(env_set, tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env_dir = str(tmp_path / "cache") if env_set else None
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CODE], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    before, used, after = out.stdout.split()
    fixed = os.path.join(ROOT, ".jax_cache")
    if not env_set:
        # importing sets nothing; the call sets the fixed in-checkout path
        assert before == "None" and used == after == fixed
    else:
        assert before == used == after == env_dir
