"""The serving launcher: its builder honours the config's dtypes, and the
CLI serves a reduced model end to end on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.serve import build, main


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "chatglm3-6b"])
def test_build_draws_params_in_the_config_dtype(arch):
    cfg, model, params = build(arch, reduced=True, seed=3)
    assert cfg == get_config(arch, reduced=True)
    leaves = jax.tree.leaves(params)
    assert {x.dtype for x in leaves if jnp.issubdtype(x.dtype, jnp.floating)} \
        == {jnp.dtype(cfg.param_dtype)}
    again = jax.tree.leaves(build(arch, reduced=True, seed=3)[2])
    assert all(bool((a == b).all()) for a, b in zip(leaves, again))


def test_serve_cli_reduced(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    main(["--arch", "tinyllama-1.1b", "--reduced", "--requests", "2",
          "--max-tokens", "2"])
    assert "served 2 requests / 4 tokens" in capsys.readouterr().out


def test_full_width_is_the_default():
    cfg = get_config("chatglm3-6b")
    assert (cfg.n_layers, cfg.d_model, cfg.param_dtype) == (28, 4096, "bfloat16")
