"""pallas: static legality checks on ``pl.pallas_call`` sites.

The paper's GVSA dataflow works because tile shapes, DSP sharing and
schedules obey statically checkable design rules; the Pallas analog has the
same flavor of invariants, checked here to the extent the AST permits:

* **PAL001** — every ``pallas_call`` declares an explicit ``grid=`` or a
  ``grid_spec=`` (e.g. ``pltpu.PrefetchScalarGridSpec``, which carries the
  grid); implicit grids hide the tiling contract.  The literal-grid checks
  below read ``grid=`` only.
* **PAL002** — when the grid is a literal tuple, every ``BlockSpec``
  index-map lambda must take exactly ``len(grid)`` arguments (an arity
  mismatch is a guaranteed lowering failure, caught here without tracing).
* **PAL003** — kernel bodies are pure: no ``time.*`` / ``random.*`` /
  ``np.random.*`` / ``os.environ`` / ``print`` / ``open`` — Python-side
  effects run once at trace time and silently disappear from the compiled
  kernel.
* **PAL004** — when every ``BlockSpec`` block shape at a call site is
  statically sizeable (int literals or module-level int constants), the
  summed per-tile operand footprint must fit the VMEM budget
  (``--vmem-budget``, default 12 MiB to match the kernels' own headroom
  constant).  Symbolic shapes are skipped — the rule proves violations,
  never absence.
* **PAL005** — literal grid x literal block shape must tile the literal
  ``out_shape`` exactly (divisibility).

Dynamic shapes (the common case in real kernels) make PAL004/PAL005
best-effort by design; the fixture suite pins the literal cases.
"""
from __future__ import annotations

import ast

from ..core import Finding, dotted_name

FAMILY = "pallas"
CODES = {
    "PAL001": "pallas_call without an explicit grid",
    "PAL002": "BlockSpec index-map arity != grid rank",
    "PAL003": "Python-side effect call inside a kernel body",
    "PAL004": "statically-sized tile footprint exceeds the VMEM budget",
    "PAL005": "literal block shape does not divide the literal out_shape",
}

_EFFECT_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.",
                    "os.environ", "os.getenv")
_EFFECT_NAMES = {"print", "open", "input", "time", "random"}


def _is_pallas_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return name == "pallas_call" or name.endswith(".pallas_call")


def _is_ctor(func: ast.AST, ctor: str) -> bool:
    name = dotted_name(func)
    return name == ctor or name.endswith("." + ctor)


def _kw(node: ast.Call, name: str):
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _module_int_constants(tree: ast.Module) -> dict[str, int]:
    out: dict[str, int] = {}
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            try:
                v = ast.literal_eval(stmt.value)
            except (ValueError, SyntaxError):
                continue
            if isinstance(v, int) and not isinstance(v, bool):
                out[stmt.targets[0].id] = v
    return out


def _static_int(node: ast.AST, consts: dict[str, int]) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) and \
            not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Mult, ast.Add, ast.Sub, ast.FloorDiv)):
        l = _static_int(node.left, consts)
        r = _static_int(node.right, consts)
        if l is None or r is None:
            return None
        if isinstance(node.op, ast.Mult):
            return l * r
        if isinstance(node.op, ast.Add):
            return l + r
        if isinstance(node.op, ast.Sub):
            return l - r
        return l // r if r else None
    return None


def _static_shape(node: ast.AST, consts) -> tuple[int, ...] | None:
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    dims = [_static_int(e, consts) for e in node.elts]
    if any(d is None for d in dims):
        return None
    return tuple(dims)  # type: ignore[arg-type]


def _blockspecs_of(call: ast.Call) -> list[ast.Call]:
    """BlockSpec constructor calls lexically inside the pallas_call's
    in_specs/out_specs keyword values (the inline-literal pattern)."""
    out = []
    for name in ("in_specs", "out_specs"):
        v = _kw(call, name)
        if v is None:
            continue
        for sub in ast.walk(v):
            if isinstance(sub, ast.Call) and \
                    _is_ctor(sub.func, "BlockSpec"):
                out.append(sub)
    return out


def _spec_name_assignments(call: ast.Call, fn) -> list[ast.Call]:
    """Resolve ``in_specs=NAME`` through assignments/augments to NAME in the
    enclosing function — only when the function holds a single pallas_call
    (several calls would alias each other's specs)."""
    names = {v.id for v in (_kw(call, "in_specs"), _kw(call, "out_specs"))
             if isinstance(v, ast.Name)}
    if not names or fn is None:
        return []
    n_calls = sum(1 for n in ast.walk(fn)
                  if isinstance(n, ast.Call) and _is_pallas_call(n))
    if n_calls != 1:
        return []
    out = []
    for stmt in ast.walk(fn):
        value = None
        if isinstance(stmt, (ast.Assign, ast.AugAssign)):
            targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
            if any(isinstance(t, ast.Name) and t.id in names for t in targets):
                value = stmt.value
        elif isinstance(stmt, ast.Call) and \
                isinstance(stmt.func, ast.Attribute) and \
                stmt.func.attr == "append" and \
                isinstance(stmt.func.value, ast.Name) and \
                stmt.func.value.id in names:
            value = stmt.args[0] if stmt.args else None
        if value is not None:
            for sub in ast.walk(value):
                if isinstance(sub, ast.Call) and \
                        _is_ctor(sub.func, "BlockSpec"):
                    out.append(sub)
    return out


def _kernel_fn_name(call: ast.Call) -> str | None:
    """The kernel body's function name: first positional arg, possibly
    wrapped in functools.partial."""
    if not call.args:
        return None
    fn = call.args[0]
    if isinstance(fn, ast.Call) and dotted_name(fn.func) in (
            "functools.partial", "partial"):
        fn = fn.args[0] if fn.args else None
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _enclosing_fn(node, sf):
    from ..core import enclosing_function
    return enclosing_function(node)


def check(index, config):
    budget = config.vmem_budget_bytes
    for sf in index.targets():
        if sf.tree is None or "pallas" not in sf.text:
            continue
        consts = _module_int_constants(sf.tree)
        kernels_checked: set[str] = set()
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call) and _is_pallas_call(node)):
                continue
            grid = _kw(node, "grid")
            if grid is None and _kw(node, "grid_spec") is not None:
                continue
            if grid is None:
                yield Finding(
                    "PAL001", FAMILY, sf.rel, node.lineno, node.col_offset,
                    "pallas_call without an explicit grid=",
                    "declare the grid — implicit whole-array kernels hide "
                    "the tiling contract the dispatch layer relies on")
                continue
            fn = _enclosing_fn(node, sf)
            specs = _blockspecs_of(node) + _spec_name_assignments(node, fn)
            # PAL002: index-map arity vs literal grid rank
            if isinstance(grid, ast.Tuple):
                rank = len(grid.elts)
                for spec in specs:
                    lam = next((a for a in spec.args
                                if isinstance(a, ast.Lambda)), None)
                    if lam is None:
                        continue
                    arity = len(lam.args.args) + len(lam.args.posonlyargs)
                    n_default = len(lam.args.defaults)
                    # defaulted trailing params are capture helpers, not
                    # grid coordinates
                    if not (arity - n_default <= rank <= arity):
                        yield Finding(
                            "PAL002", FAMILY, sf.rel, spec.lineno,
                            spec.col_offset,
                            f"BlockSpec index map takes {arity} args but the "
                            f"grid has rank {rank}",
                            "the index map receives one program id per grid "
                            "axis — an arity mismatch fails at lowering")
            # PAL004: statically-sized tile footprint vs the VMEM budget
            tile_bytes = 0
            all_static = bool(specs)
            for spec in specs:
                shape = _static_shape(spec.args[0], consts) if spec.args else None
                if shape is None:
                    all_static = False
                    break
                n = 1
                for d in shape:
                    n *= d
                tile_bytes += n * 4  # f32 worst case per operand tile
            if all_static and tile_bytes > budget:
                yield Finding(
                    "PAL004", FAMILY, sf.rel, node.lineno, node.col_offset,
                    f"summed tile footprint ~{tile_bytes // 1024} KiB exceeds "
                    f"the VMEM budget ({budget // 1024} KiB)",
                    "shrink the block shapes or raise --vmem-budget if the "
                    "target really has more on-chip memory")
            # PAL005: literal grid x literal out block must tile out_shape
            yield from _check_divisibility(sf, node, grid, consts)
            # PAL003: kernel body purity
            kname = _kernel_fn_name(node)
            if kname and kname not in kernels_checked:
                kernels_checked.add(kname)
                yield from _check_kernel_purity(sf, kname)


def _check_divisibility(sf, node, grid, consts):
    out_shape = _kw(node, "out_shape")
    out_specs = _kw(node, "out_specs")
    if not isinstance(grid, ast.Tuple) or out_shape is None or \
            out_specs is None:
        return
    grid_dims = [_static_int(e, consts) for e in grid.elts]
    if any(d is None for d in grid_dims):
        return
    # single ShapeDtypeStruct + single BlockSpec only (the common literal
    # fixture shape); multi-output kernels are skipped
    if not (isinstance(out_shape, ast.Call) and
            _is_ctor(out_shape.func, "ShapeDtypeStruct")):
        return
    shape = _static_shape(out_shape.args[0], consts) if out_shape.args else None
    if shape is None:
        return
    spec = out_specs if isinstance(out_specs, ast.Call) else None
    if spec is None or not _is_ctor(spec.func, "BlockSpec"):
        return
    block = _static_shape(spec.args[0], consts) if spec.args else None
    if block is None or len(block) != len(shape):
        return
    for i, (b, s) in enumerate(zip(block, shape)):
        if b and s % b:
            yield Finding(
                "PAL005", FAMILY, sf.rel, spec.lineno, spec.col_offset,
                f"block dim {i} ({b}) does not divide out_shape dim "
                f"{i} ({s})",
                "pad the array to a block multiple (the repo's kernels pad "
                "then slice) or pick a dividing block shape")


def _check_kernel_purity(sf, kernel_name):
    for node in ast.walk(sf.tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and
                node.name == kernel_name):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = dotted_name(sub.func)
            bad = name in _EFFECT_NAMES or \
                any(name.startswith(p) for p in _EFFECT_PREFIXES)
            if bad:
                yield Finding(
                    "PAL003", FAMILY, sf.rel, sub.lineno, sub.col_offset,
                    f"kernel body {kernel_name}() calls {name}()",
                    "kernel bodies trace once and run on device — Python-"
                    "side RNG/time/IO executes at trace time and vanishes "
                    "from the compiled kernel")
