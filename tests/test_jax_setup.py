"""The compile-cache placement rule (utils/jax_setup.py): with
``JAX_COMPILATION_CACHE_DIR`` set JAX reads it itself and the code sets
no directory at all; unset, every process started from one checkout
uses the fixed ``<checkout>/.jax_cache`` — the directory is part of the
cache key, so a path that moved with the host or the process never hit.
"""
import os
import subprocess
import sys

import jax

from transmogrifai_tpu.utils import jax_setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    seen = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (seen.append(name), real(name, value))[1])
    return seen


def test_env_set_means_no_directory_set_in_code(monkeypatch, tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    seen = _recorded_updates(monkeypatch)
    assert jax_setup.enable_compilation_cache() == placed
    assert "jax_compilation_cache_dir" not in seen
    assert not os.path.exists(placed)     # nor created: JAX owns it
    assert jax_setup.backend_block()["compile_cache"]["dir"] == placed


def test_env_unset_is_the_fixed_checkout_dir_from_any_process(
        monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = _recorded_updates(monkeypatch)
    here = jax_setup.enable_compilation_cache()
    assert here == os.path.join(ROOT, ".jax_cache")
    assert "jax_compilation_cache_dir" in seen
    assert jax.config.jax_compilation_cache_dir == here
    # a second process, started elsewhere, lands on the same path
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, "-c",
         "from transmogrifai_tpu.utils.jax_setup import "
         "enable_compilation_cache as e; print(e())"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == here
