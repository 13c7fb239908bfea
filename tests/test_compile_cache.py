"""Where the entry points keep JAX's persistent compilation cache."""
import os
import subprocess
import sys

import jax

from repro import compat

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compat.use_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_environment_is_the_only_one(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the CLI's compiled programs
    land there and the in-checkout default stays untouched."""
    default = os.path.join(ROOT, ".jax_cache")
    seen = set(os.listdir(default)) if os.path.isdir(default) else set()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate", "--hosts", "8",
         "--vms", "4", "--waves", "2"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "completed=8" in proc.stdout
    assert any(tmp_path.iterdir())
    now = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert now == seen
