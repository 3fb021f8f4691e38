"""Where the entry points put JAX's persistent compilation cache.

Each case runs in its own interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` when it is imported, and the test process
itself must keep the cache off.  Nothing is compiled.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
_PROBE = ("import jax; from repro.launch.compile_cache import "
          "enable_compile_cache; d = enable_compile_cache(); "
          "print(d); print(jax.config.jax_compilation_cache_dir)")


def _probe(cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


@pytest.mark.parametrize("explicit", [False, True])
def test_cache_dir(explicit, tmp_path):
    want = str(tmp_path / "cache") if explicit else str(REPO / ".jax_cache")
    returned, configured = _probe(want if explicit else None)
    assert returned == configured == want


def test_repo_cache_dir_is_ignored_by_git():
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
