"""A configuration file -> the served model, and its weights from a seed.

A configuration is a JSON file under ``bench/configs/``.  Its ``model``
object states every size as it is served; :func:`served_config` builds the
program's ``ModelConfig`` from the program's registry entry named by
``program_arch`` and the file's sizes, and refuses to run when the two
disagree on a key the file does not list in ``changed_from_registry``.

The weights are made here, not by the program: :func:`make_params` draws
every leaf of the program's parameter layout from the seed, in one jitted
call, on the device, in the dtype it is served in.  The plain reference
reads the same arrays, so the check compares two computations on weights
that neither of them made.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# ModelConfig keys a configuration file states, in the file's "model" object
MODEL_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads",
              "head_dim", "d_ff", "vocab_size", "qkv_bias", "partial_rotary",
              "rope_theta", "tie_embeddings", "norm_type", "act", "pos_type",
              "window", "param_dtype", "compute_dtype")


def load_config(path: str | Path) -> dict:
    with open(path) as f:
        return json.load(f)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, high bits included (a plain
    ``PRNGKey(seed)`` keeps only the low 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def tt_roles(cj: dict) -> dict[str, dict]:
    """{role: {"in_modes", "out_modes", "ranks"}} with the ranks clamped to
    the largest a TT of those modes can have (the format's own bound)."""
    ttd = cj["model"]["ttd"]
    out = {}
    for role, r in ttd["roles"].items():
        n, m = r["in_modes"], r["out_modes"]
        v = [a * b for a, b in zip(m, n)]
        ranks = [1] + [r["rank"]] * (len(n) - 1) + [1]
        for k in range(1, len(n)):
            ranks[k] = min(ranks[k], math.prod(v[:k]), math.prod(v[k:]))
        out[role] = {"in_modes": tuple(n), "out_modes": tuple(m),
                     "ranks": tuple(ranks)}
    return out


def served_config(cj: dict, *, kernel_backend: str | None = None):
    """The program's ``ModelConfig`` for configuration ``cj``."""
    from repro.config import TTDConfig, TTLayerOverride
    from repro.configs import get_config

    mj = cj["model"]
    base = get_config(cj["program_arch"])
    ttd = mj["ttd"]
    overrides = tuple(
        (role, TTLayerOverride(in_modes=tuple(r["in_modes"]),
                               out_modes=tuple(r["out_modes"]), rank=r["rank"]))
        for role, r in ttd["roles"].items())
    cfg = base.replace(
        **{k: mj[k] for k in MODEL_KEYS},
        ttd=TTDConfig(enabled=True, rank=ttd["rank"], d=ttd["d"],
                      overrides=overrides,
                      first_tt_block=ttd["first_tt_block"]))
    changed = set(cj.get("changed_from_registry", ()))
    differ = sorted(k for k in MODEL_KEYS
                    if getattr(base, k) != getattr(cfg, k) and k not in changed)
    if differ:
        raise ValueError(f"{cj['name']}: sizes differ from the program's "
                         f"{cj['program_arch']!r} entry without being listed "
                         f"in changed_from_registry: {differ}")
    if kernel_backend is not None:
        cfg = cfg.replace(kernel_backend=kernel_backend)
    return cfg


def _leaf_rule(path: str, shape, cj: dict) -> tuple[float, float]:
    """``(a, b)``: one leaf of the parameter tree is ``a + b * N(0, 1)``."""
    mj = cj["model"]
    last = path.rsplit("/", 1)[-1]
    if last == "scale":  # norm gains
        return 1.0, 0.1
    if last == "b":  # biases of the q/k/v projections
        return 0.0, 0.2
    if last == "table":  # embedding (and the tied unembedding)
        return 0.0, 1.0 / math.sqrt(mj["d_model"])
    if "/cores/" in path:
        role = cj["_core_roles"][path.split("/cores/")[0]]
        tt = tt_roles(cj)[role]
        k = int(path.rsplit("/", 1)[1])
        want = (tt["ranks"][k] * tt["in_modes"][k],
                tt["out_modes"][k] * tt["ranks"][k + 1])
        if tuple(shape[-2:]) != want:
            raise ValueError(f"{path}: the program holds a core of shape "
                             f"{tuple(shape[-2:])}, the configuration's modes "
                             f"give {want}")
        n_in = math.prod(tt["in_modes"])
        r_int = math.prod(tt["ranks"][1:-1])
        # the implied dense weight has variance 1 / n_in
        return 0.0, (1.0 / (n_in * r_int)) ** (1.0 / (2 * len(tt["in_modes"])))
    if last == "w":  # dense (…, n_in, n_out)
        return 0.0, 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no rule for parameter {path}")


def _draw(key, shape, dtype, a: float, b: float):
    """``a + b * N(0, 1)`` in ``dtype``.  A leaf stacked over layers is
    drawn one layer at a time, so that no float32 copy of a whole stack is
    held beside the served weights."""
    def one(k, s):
        return (a + b * jax.random.normal(k, s, jnp.float32)).astype(dtype)

    if len(shape) < 3:
        return one(key, shape)
    return jax.lax.map(lambda k: one(k, shape[1:]), jax.random.split(key, shape[0]))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


CORE_ROLE = {"attn/wo": "attn_o", "mlp/gate": "mlp_gate", "mlp/up": "mlp_up",
             "mlp/down": "mlp_down"}


def make_params(model, cj: dict, seed: int):
    """Every parameter of ``model`` drawn from ``seed`` in one jitted call."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path_str(p) for p, _ in flat]
    cj = dict(cj, _core_roles={
        p.split("/cores/")[0]: CORE_ROLE[p.split("/cores/")[0].split("/", 2)[2]]
        for p in paths if "/cores/" in p})

    def draw(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_draw(k, s.shape, s.dtype, *_leaf_rule(p, s.shape, cj))
                  for k, p, (_, s) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key_from_seed(seed))
