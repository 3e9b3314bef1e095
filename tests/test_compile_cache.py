"""The persistent compilation cache: an exported directory wins, otherwise a
fixed directory inside the checkout, and importing ``repro`` sets neither."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR, REPO_ROOT, use_compile_cache


def test_exported_cache_dir_is_kept(monkeypatch, tmp_path):
    exported = str(tmp_path / "exported")
    monkeypatch.setenv(ENV_VAR, exported)
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == exported
    assert jax.config.jax_compilation_cache_dir == before  # nothing set over it


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = use_compile_cache(), use_compile_cache()
        assert first == second == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_DIR == REPO_ROOT / ".jax_cache"
    assert (REPO_ROOT / "pyproject.toml").is_file()
    assert (REPO_ROOT / "src" / "repro").is_dir()


def test_importing_repro_sets_no_cache():
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(Path(REPO_ROOT) / "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro, repro.models, repro.serve.engine, repro.launch.serve;"
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "None"
