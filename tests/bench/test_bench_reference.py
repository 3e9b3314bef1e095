"""The plain reference agrees with the program where both compute in
float32, and the float8 control does not."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(ROOT / "bench"))

import check  # noqa: E402
import model as bench_model  # noqa: E402
from repro.core.ttd import TTSpec, matrices_to_cores, tt_reconstruct  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.sessions import SessionSpec, make_session  # noqa: E402
from repro.serve import steps  # noqa: E402

S = 40


@pytest.fixture(scope="module")
def tiny():
    cp = FIX / "tiny-tt.json"
    cj = bench_model.load_config(cp)
    cfg = bench_model.served_config(cj, kernel_backend="ref")
    params = bench_model.make_params(build_model(cfg), cj, 2**40 + 7)
    return cp, cj, cfg, params


def _program_logits(cfg, params, tokens, compute_dtype):
    cfg = cfg.replace(compute_dtype=compute_dtype)
    sess = make_session(cfg, SessionSpec(slots=2, max_len=64, prefill_chunk=64,
                                         cache_dtype=compute_dtype), backend="paged")
    prefill, _, _ = steps.session_step_fns(sess, "ref")
    table = np.zeros((2, 4), np.int32)
    table[0] = [1, 2, 3, 4]
    state = sess.with_tables(sess.init_state(), table)
    tok = np.zeros((2, 64), np.int32)
    pos = np.full((2, 64), -1, np.int32)
    tok[0, :S], pos[0, :S] = tokens, np.arange(S)
    logits, _ = prefill(params, state, jnp.asarray(tok), jnp.asarray(pos))
    return np.asarray(logits[0, :S])


def _reference_logits(cp, cj, params, tokens, quant=None):
    ref = check.load_reference(cp, cj)
    pad = np.zeros(512, np.int32)
    pad[:S] = tokens
    fwd = jax.jit(lambda p, t, k: ref.forward(p, cj["model"], bench_model.tt_roles(cj),
                                              t, k, quant=quant))
    return np.asarray(fwd(params, jnp.asarray(pad), jnp.arange(S)))


def test_tt_weight_matches_the_format():
    spec = TTSpec.make(96, 64, 4, in_modes=(6, 4, 4), out_modes=(4, 4, 4))
    rng = np.random.default_rng(0)
    cores = [rng.standard_normal(s).astype(np.float32) for s in spec.core_matrix_shapes()]
    ref = check.load_reference(FIX / "tiny-tt.json",
                               bench_model.load_config(FIX / "tiny-tt.json"))
    ours = np.asarray(ref.tt_dense_weight([jnp.asarray(c) for c in cores],
                                          spec.in_modes, spec.out_modes, spec.ranks))
    w = tt_reconstruct(matrices_to_cores(cores, spec), spec)  # (M, N)
    np.testing.assert_allclose(ours, w.T, rtol=1e-5, atol=1e-5)


def test_reference_is_the_programs_model_in_float32(tiny):
    cp, cj, cfg, params = tiny
    tokens = np.random.default_rng(0).integers(1, 512, S)
    prog = _program_logits(cfg, params, tokens, "float32")
    ref = _reference_logits(cp, cj, params, tokens)
    assert np.linalg.norm(prog - ref) / np.linalg.norm(ref) < 1e-5


def test_float8_control_is_far_from_bf16_noise(tiny):
    cp, cj, cfg, params = tiny
    tokens = np.random.default_rng(1).integers(1, 512, S)
    ref = _reference_logits(cp, cj, params, tokens)
    bf16 = _program_logits(cfg, params, tokens, "bfloat16")
    f8 = _reference_logits(cp, cj, params, tokens, quant="fp8")
    err = lambda x: np.linalg.norm(x - ref) / np.linalg.norm(ref)  # noqa: E731
    assert err(bf16) < 0.02
    assert err(f8) > 4 * err(bf16)


def test_served_config_refuses_unlisted_changes():
    cj = bench_model.load_config(FIX / "tiny-tt.json")
    cj["changed_from_registry"] = [k for k in cj["changed_from_registry"]
                                   if k != "d_ff"]
    with pytest.raises(ValueError, match="d_ff"):
        bench_model.served_config(cj)


@pytest.mark.parametrize("name", ["chatglm3-6b-tt"])
def test_benchmark_configs_are_the_registry_entries_as_stated(name):
    cj = bench_model.load_config(ROOT / "bench" / "configs" / f"{name}.json")
    cfg = bench_model.served_config(cj)
    for key, value in cj["model"].items():
        if key not in ("ttd", "norm_eps"):
            assert getattr(cfg, key) == value, key
    assert cfg.ttd.first_tt_block == cj["model"]["ttd"]["first_tt_block"]
    for role, tt in bench_model.tt_roles(cj).items():
        spec = TTSpec.make(int(np.prod(tt["in_modes"])), int(np.prod(tt["out_modes"])),
                           cj["model"]["ttd"]["rank"], in_modes=tt["in_modes"],
                           out_modes=tt["out_modes"])
        assert spec.ranks == tt["ranks"], role
