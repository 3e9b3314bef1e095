"""The plain reference agrees with the program where both compute in
float32, and the float8 control does not; a configuration file states the
served model, its TT roles and its weight rules."""
import copy
import hashlib
import json
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
from repro.config import TTDConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
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


SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_benchmark_configs_are_the_registry_entries_as_stated(entry):
    cj = bench_model.load_config(ROOT / entry["file"])
    cfg = bench_model.served_config(cj)
    skip = set(bench_model.REFERENCE_ONLY) | set(cj.get("reference_only", ()))
    for key, value in cj["model"].items():
        if key not in skip:
            want = tuple(value) if isinstance(value, list) else value
            assert getattr(cfg, key) == want, key
    ttd = cj["model"].get("ttd")
    assert cfg.ttd.enabled == bool(ttd)
    if ttd:
        assert cfg.ttd.first_tt_block == ttd["first_tt_block"]
    for role, tt in bench_model.tt_roles(cj).items():
        spec = TTSpec.make(int(np.prod(tt["in_modes"])), int(np.prod(tt["out_modes"])),
                           ttd["rank"], in_modes=tt["in_modes"],
                           out_modes=tt["out_modes"])
        assert spec.ranks == tt["ranks"], role


def test_chatglm3_is_served_as_before():
    # the parent commit of this test built exactly the registry's entry from
    # this file (checked there): every field, the TT recipe included
    cj = bench_model.load_config(ROOT / "bench" / "configs" / "chatglm3-6b-tt.json")
    assert bench_model.served_config(cj) == get_config("chatglm3-6b")
    assert bench_model.served_config(cj, kernel_backend="pallas") == \
        get_config("chatglm3-6b").replace(kernel_backend="pallas")


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# make_params on tiny-tt at seed 2**40 + 7, computed on the parent commit of
# this test (before the TT roles' paths and the leaf rules became the
# configuration's): the draws, their keys and their order are unchanged
TINY_TT_DIGEST = "aa0a2c4cf6cb671ddaa6fa1bce2e0ff385035d6cfd9fd9ace1aaedf9843675df"


def test_tiny_tt_weights_are_drawn_bit_identically(tiny):
    assert _digest(tiny[3]) == TINY_TT_DIGEST


def test_a_cores_role_follows_from_its_shapes(tiny):
    cp, cj, cfg, params = tiny
    tt = bench_model.tt_roles(cj)
    owners = {f"segments/1/{p}": bench_model._core_shapes(tt[r]) for p, r in (
        ("attn/wo", "attn_o"), ("mlp/gate", "mlp_gate"), ("mlp/up", "mlp_up"),
        ("mlp/down", "mlp_down"))}
    # gate and up have the same modes and ranks, so they draw alike
    assert bench_model.core_roles(cj, owners) == {
        "segments/1/attn/wo": "attn_o", "segments/1/mlp/gate": "mlp_gate",
        "segments/1/mlp/up": "mlp_gate", "segments/1/mlp/down": "mlp_down"}
    # the roles' order in the file draws the same leaves
    cj = copy.deepcopy(cj)
    roles = cj["model"]["ttd"]["roles"]
    cj["model"]["ttd"]["roles"] = dict(reversed(list(roles.items())))
    assert _digest(bench_model.make_params(build_model(cfg), cj, 2**40 + 7)) == \
        TINY_TT_DIGEST


def test_a_core_that_matches_no_role_is_an_error_naming_its_path(tiny):
    cp, cj, cfg, params = tiny
    cj = copy.deepcopy(cj)
    cj["model"]["ttd"]["roles"]["attn_o"]["in_modes"] = [8, 2, 4]
    with pytest.raises(ValueError, match="segments/1/attn/wo: TT cores of shapes .* which "
                                         "no TT role of the configuration gives"):
        bench_model.make_params(build_model(cfg), cj, 1)


def test_served_config_refuses_a_key_it_does_not_know():
    cj = bench_model.load_config(FIX / "tiny-tt.json")
    cj["model"]["q_lora_rank"] = 8  # not a field of the program's ModelConfig
    with pytest.raises(ValueError, match="q_lora_rank"):
        bench_model.served_config(cj)
    cj["reference_only"] = ["q_lora_rank"]  # read by a reference alone
    assert bench_model.served_config(cj) == \
        bench_model.served_config(bench_model.load_config(FIX / "tiny-tt.json"))


def test_reference_only_names_no_field_of_the_program():
    # a field listed there would reach the reference and not the program,
    # and go unchecked against the registry
    cj = bench_model.load_config(FIX / "tiny-moe.json")
    cj["reference_only"] = cj["reference_only"] + ["d_ff_expert"]
    with pytest.raises(ValueError, match=r"reference_only names fields .*\['d_ff_expert'\]"):
        bench_model.served_config(cj)


def test_without_ttd_the_model_has_no_tt_layers():
    cj = bench_model.load_config(FIX / "tiny-tt.json")
    del cj["model"]["ttd"]
    cfg = bench_model.served_config(cj)
    assert cfg.ttd == TTDConfig()
    assert bench_model.tt_roles(cj) == {}
    params = bench_model.make_params(build_model(cfg), cj, 1)
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert not any("cores" in p for p in paths)


def test_the_references_leaf_rule_comes_first():
    cp = FIX / "tiny-moe.json"
    cj = bench_model.load_config(cp)
    cfg = bench_model.served_config(cj)
    ref = check.load_reference(cp, cj)
    own = bench_model.make_params(build_model(cfg), cj, 5, leaf_rule=ref.leaf_rule)
    plain = bench_model.make_params(build_model(cfg), cj, 5)
    router = lambda p: np.asarray(p["segments"][0]["moe"]["router"]["w"])  # noqa: E731
    experts = lambda p: np.asarray(p["segments"][0]["moe"]["experts"]["up"]["w"])  # noqa: E731
    # the same draws, scaled by the rule that holds for the leaf
    np.testing.assert_allclose(router(own), router(plain) * 0.5 * np.sqrt(64), rtol=1e-6)
    np.testing.assert_array_equal(experts(own), experts(plain))
    assert router(own).std() == pytest.approx(cj["model"]["router_std"], rel=0.1)
