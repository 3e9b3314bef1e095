"""A configuration file -> the served model, and its weights from a seed.

A configuration is a JSON file under ``bench/configs/``.  Its ``model``
object states the served model: every key that is a field of the program's
``ModelConfig`` is passed to it (a JSON list as a tuple), and is checked
against the program's registry entry named by ``program_arch`` unless the
file lists it in ``changed_from_registry``.  The other keys are read by the
plain reference alone: ``norm_eps``, ``ttd`` and whatever the file lists
under ``reference_only`` (which may name no field); any other key is
refused.  Without ``ttd`` the served model has no Tensor-Train layers; with
it, a layer's TT cores belong to the role whose modes and ranks give their
shapes.  The file may name an ``ops`` module beside it that counts the
served model's operations (``sequence_flops``) and each kernel's operations
and bytes (``KERNELS``); by default ``bench/opcount.py``.

The weights are made here, not by the program: :func:`make_params` draws
every leaf of the program's parameter layout from the seed, in one jitted
call, on the device, in the dtype it is served in.  A leaf's rule comes
first from the reference module's ``leaf_rule``, where it has one, then
from the rules here.  The plain reference reads the same arrays, so the
check compares two computations on weights that neither of them made.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# model keys only the plain reference reads
REFERENCE_ONLY = ("norm_eps", "ttd")


def load_config(path: str | Path) -> dict:
    with open(path) as f:
        return json.load(f)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, high bits included (a plain
    ``PRNGKey(seed)`` keeps only the low 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def load_module(path: str | Path, prefix: str = "bench"):
    """The Python file at ``path`` as a module of its own."""
    path = Path(path)
    spec = importlib.util.spec_from_file_location(f"{prefix}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ops_module(config_path: str | Path, cj: dict):
    """The module that counts the configuration's operations: the file's
    ``ops``, beside it, or :mod:`opcount`."""
    if "ops" not in cj:
        import opcount
        return opcount
    return load_module(Path(config_path).parent / cj["ops"], "ops")


def tt_roles(cj: dict) -> dict[str, dict]:
    """{role: {"in_modes", "out_modes", "ranks"}} with the ranks clamped to
    the largest a TT of those modes can have (the format's own bound);
    empty without ``ttd``."""
    ttd = cj["model"].get("ttd")
    out = {}
    for role, r in (ttd["roles"].items() if ttd else ()):
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
    from repro.config import ModelConfig, TTDConfig, TTLayerOverride
    from repro.configs import get_config

    mj = cj["model"]
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    listed = set(cj.get("reference_only", ()))
    if listed & fields:
        raise ValueError(f"{cj['name']}: reference_only names fields of the "
                         f"program's ModelConfig, which the program has to be "
                         f"given: {sorted(listed & fields)}")
    reference_only = set(REFERENCE_ONLY) | listed
    unknown = sorted(k for k in mj if k not in fields | reference_only)
    if unknown:
        raise ValueError(f"{cj['name']}: model keys that are neither fields of "
                         f"the program's ModelConfig nor listed in "
                         f"reference_only: {unknown}")
    stated = {k: tuple(v) if isinstance(v, list) else v
              for k, v in mj.items() if k not in reference_only}
    nested = sorted(k for k, v in stated.items() if isinstance(v, dict))
    if nested:
        raise ValueError(f"{cj['name']}: model keys with an object value are "
                         f"not passed to the program: {nested}")
    ttd = mj.get("ttd")
    if ttd:
        overrides = tuple(
            (role, TTLayerOverride(in_modes=tuple(r["in_modes"]),
                                   out_modes=tuple(r["out_modes"]),
                                   rank=r["rank"]))
            for role, r in ttd["roles"].items())
        tt = TTDConfig(enabled=True, rank=ttd["rank"], d=ttd["d"],
                       overrides=overrides, first_tt_block=ttd["first_tt_block"])
    else:
        tt = TTDConfig()
    base = get_config(cj["program_arch"])
    cfg = base.replace(**stated, ttd=tt)
    changed = set(cj.get("changed_from_registry", ()))
    differ = sorted(k for k in stated
                    if getattr(base, k) != getattr(cfg, k) and k not in changed)
    if differ:
        raise ValueError(f"{cj['name']}: sizes differ from the program's "
                         f"{cj['program_arch']!r} entry without being listed "
                         f"in changed_from_registry: {differ}")
    if kernel_backend is not None:
        cfg = cfg.replace(kernel_backend=kernel_backend)
    return cfg


def _core_shapes(tt: dict) -> list[tuple[int, int]]:
    """The (rows, columns) of each TT core of one role."""
    return [(tt["ranks"][k] * n, m * tt["ranks"][k + 1])
            for k, (n, m) in enumerate(zip(tt["in_modes"], tt["out_modes"]))]


def core_roles(cj: dict, cores: dict[str, list]) -> dict[str, str]:
    """{owner path of TT cores: the TT role whose modes and ranks give the
    shapes of its cores, ``cores[owner]`` in order}.  Roles that give the
    same shapes draw alike, so the first is taken.  An owner whose cores no
    role gives is an error that names it."""
    tt = tt_roles(cj)
    out = {}
    for owner, shapes in cores.items():
        hit = [role for role, t in tt.items() if _core_shapes(t) == shapes]
        if not hit:
            raise ValueError(f"{owner}: TT cores of shapes {shapes}, which no "
                             f"TT role of the configuration gives")
        out[owner] = hit[0]
    return out


def _leaf_rule(path: str, shape, cj: dict, roles: dict) -> tuple[float, float]:
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
        tt = tt_roles(cj)[roles[path.split("/cores/")[0]]]
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


def make_params(model, cj: dict, seed: int, *, leaf_rule=None):
    """Every parameter of ``model`` drawn from ``seed`` in one jitted call.

    ``leaf_rule(path, shape, cj)``, a reference module's, gives ``(a, b)``
    for the leaves it knows and ``None`` for the rest, which the rules here
    draw."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path_str(p) for p, _ in flat]
    cores: dict[str, dict[int, tuple]] = {}
    for p, (_, s) in zip(paths, flat):
        if "/cores/" in p:
            owner, k = p.split("/cores/")
            cores.setdefault(owner, {})[int(k)] = tuple(s.shape[-2:])
    roles = core_roles(cj, {o: [c[k] for k in sorted(c)] for o, c in cores.items()})

    def rule(path, shape):
        got = leaf_rule(path, shape, cj) if leaf_rule is not None else None
        return got if got is not None else _leaf_rule(path, shape, cj, roles)

    def draw(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_draw(k, s.shape, s.dtype, *rule(p, s.shape))
                  for k, p, (_, s) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key_from_seed(seed))
