import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.config import (QuantConfig, ShapeCell, TrainConfig,  # noqa: E402
                          shape_cell)
from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, get_config  # noqa: E402
from repro.dist.sharding import param_pspecs, param_shardings  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import init_optimizer  # noqa: E402
from repro.serve.steps import cache_shardings, serve_config_of  # noqa: E402
from repro.train.step import (TrainState, batch_pspec, build_train_step,  # noqa: E402
                              state_pspecs)

# ---------------------------------------------------------------------------
# Cell policy (DESIGN.md §5)
# ---------------------------------------------------------------------------
SUBQUADRATIC = {"rwkv6-7b", "recurrentgemma-2b", "mixtral-8x22b"}
BIG_TRAIN = {"kimi-k2-1t-a32b", "qwen1.5-110b", "mixtral-8x22b"}  # adafactor+mb4
# bf16 sharded params (f32 optimizer math) halves FSDP all-gather traffic;
# hillclimb-2 result, see EXPERIMENTS.md §Perf
BF16_PARAMS = BIG_TRAIN | {"recurrentgemma-2b", "rwkv6-7b"}


def cell_skip_reason(arch: str, cell: ShapeCell) -> str | None:
    if cell.name == "long_500k" and arch not in SUBQUADRATIC:
        return "long_500k requires sub-quadratic attention; skipped for pure full-attention archs"
    return None


def arch_cell_config(arch: str, cell: ShapeCell, *, baseline: bool = False,
                     reduced: bool = False):
    cfg = get_config(arch, reduced=reduced)
    if baseline:
        cfg = cfg.replace(ttd=cfg.ttd.__class__(enabled=False))
    if cell.kind == "train":
        cfg = cfg.replace(quant=QuantConfig(enabled=False),
                          param_dtype="bfloat16" if arch in BF16_PARAMS else "float32")
    else:
        cfg = serve_config_of(cfg)
    if cell.seq_len > cfg.max_seq_len:
        cfg = cfg.replace(max_seq_len=cell.seq_len)
    if os.environ.get("DRYRUN_MOE_IMPL"):
        cfg = cfg.replace(moe_impl=os.environ["DRYRUN_MOE_IMPL"])
    # record the env's dispatch backend on the config itself: the env already
    # outranks cfg in resolve_backend's chain, but pinning here makes the
    # lowered program reproducible from cfg alone (env may change pre-trace)
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        cfg = cfg.replace(kernel_backend=os.environ["REPRO_KERNEL_BACKEND"])
    return cfg


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "s16": 2, "u16": 2, "pred": 1, "s64": 8, "u64": 8}
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_TYPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(ls: str, n_dev: int) -> int:
    m = _GROUPS_IOTA_RE.search(ls)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(ls)
    if m:
        return len(m.group(1).split(","))
    return n_dev


def collective_bytes(hlo_text: str, n_dev: int = 256) -> dict:
    """Per-device collective traffic by op kind, from the post-SPMD HLO.

    Result bytes are local (post-partition); link traffic per device is
    modeled for ring algorithms over groups of size g:
      all-gather        out·(g-1)/g     (out = full gathered tensor)
      reduce-scatter    out·(g-1)       (out = one shard)
      all-reduce        2·out·(g-1)/g
      all-to-all        out·(g-1)/g
      collective-permute out
    ``*_raw`` fields keep the unweighted result-byte sums."""
    out = {k: 0.0 for k in _COLL_OPS}
    raw = {k: 0 for k in _COLL_OPS}
    out_count = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls or "=" not in ls:
            continue
        for op in _COLL_OPS:
            # `-start` lines carry the payload type; skip `-done` (would
            # double-count async collectives)
            if re.search(rf"\b{op}-done\(", ls):
                break
            if re.search(rf"\b{op}(-start)?\(", ls):
                lhs = ls.split("=", 1)[1]
                lhs = lhs.split("(", 1)[0]  # result type section
                b = sum(_shape_bytes(m) for m in _TYPE_RE.finditer(lhs))
                g = max(_group_size(ls, n_dev), 1)
                mult = {"all-gather": (g - 1) / g,
                        "reduce-scatter": (g - 1),
                        "all-reduce": 2 * (g - 1) / g,
                        "all-to-all": (g - 1) / g,
                        "collective-permute": 1.0}[op]
                raw[op] += b
                out[op] += b * mult
                out_count += 1
                break
    rec = {k: out[k] for k in _COLL_OPS}
    rec.update({f"{k}_raw": raw[k] for k in _COLL_OPS})
    rec["count"] = out_count
    rec["total"] = sum(out[k] for k in _COLL_OPS)
    rec["total_raw"] = sum(raw[k] for k in _COLL_OPS)
    return rec


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------
def lower_cell(arch: str, cell: ShapeCell, mesh, *, baseline: bool = False,
               optimizer: str | None = None, reduced: bool = False):
    """Lower + compile one (arch × cell) on ``mesh``; return artifacts."""
    cfg = arch_cell_config(arch, cell, baseline=baseline, reduced=reduced)
    model = build_model(cfg)
    batch = input_specs(cfg, cell)

    with jax.set_mesh(mesh):
        if cell.kind == "train":
            opt = optimizer or ("adafactor" if arch in BIG_TRAIN else "adamw")
            mb = 4 if arch in BIG_TRAIN else 1  # cuts activation temps 4x
            tc = TrainConfig(global_batch=cell.global_batch, seq_len=cell.seq_len,
                             optimizer=opt, remat="full", microbatches=mb)
            step = build_train_step(model, tc)
            specs = state_pspecs(model, tc, mesh)
            state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                    is_leaf=lambda x: isinstance(x, P))
            bspec = jax.tree.map(
                lambda x: NamedSharding(mesh, batch_pspec(mesh, len(x.shape) - 1)),
                batch)
            if "positions" in batch:  # (3, B, S): batch is dim 1
                bspec["positions"] = NamedSharding(
                    mesh, P(None, ("pod", "data") if "pod" in mesh.axis_names else "data", None))
            def _make_state(key):
                params = model.init(key)
                return TrainState(params=params,
                                  opt=init_optimizer(tc.optimizer, params),
                                  step=jnp.zeros((), jnp.int32))

            state_shapes = jax.eval_shape(_make_state, jax.random.PRNGKey(0))
            jitted = jax.jit(step, in_shardings=(state_sh, bspec),
                             out_shardings=(state_sh, None))
            lowered = jitted.lower(state_shapes, batch)
        elif cell.kind == "prefill":
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            psh = param_shardings(pshapes, mesh, fsdp=False)
            bspec = jax.tree.map(
                lambda x: NamedSharding(mesh, batch_pspec(mesh, len(x.shape) - 1)),
                batch)
            if "positions" in batch:
                bspec["positions"] = NamedSharding(
                    mesh, P(None, ("pod", "data") if "pod" in mesh.axis_names else "data", None))

            def prefill_step(params, b):
                return model.prefill(params, b, max_len=cell.seq_len)

            jitted = jax.jit(prefill_step, in_shardings=(psh, bspec))
            lowered = jitted.lower(pshapes, batch)
        else:  # decode
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            psh = param_shardings(pshapes, mesh, fsdp=False)
            cache_dt = getattr(jnp, os.environ.get("DRYRUN_CACHE_DTYPE", "bfloat16"))
            cshapes = jax.eval_shape(
                lambda: model.init_cache(cell.global_batch, cell.seq_len, cache_dt))
            csh = cache_shardings(cshapes, mesh)
            bax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
            n_b = 1
            for a in bax:
                n_b *= mesh.shape[a]
            bax = bax if cell.global_batch % n_b == 0 else None
            bspec = {"tokens": NamedSharding(mesh, P(bax, None))}
            if "positions" in batch:
                bspec["positions"] = NamedSharding(mesh, P(None, bax, None))

            def serve_step(params, cache, b, pos):
                return model.decode_step(params, cache, b, pos)

            jitted = jax.jit(serve_step, in_shardings=(psh, csh, bspec, None))
            lowered = jitted.lower(pshapes, cshapes, batch,
                                   jax.ShapeDtypeStruct((), jnp.int32))

    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    return cfg, lowered, compiled, compile_s


def analyze(lowered, compiled, mesh) -> dict:
    n_dev = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_d = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "peak_memory_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_d[f] = int(v)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, int(n_dev))
    return {
        "devices": int(n_dev),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", cost.get("bytes_accessed", 0.0))),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "memory": mem_d,
        "collectives": coll,
        "hlo_ops": len(hlo.splitlines()),
    }


def run_cell(arch: str, cell_name: str, multi_pod: bool, out_dir: Path,
             baseline: bool = False, mesh=None, reduced: bool = False,
             cell: ShapeCell | None = None) -> dict:
    cell = cell or shape_cell(cell_name)
    skip = cell_skip_reason(arch, cell)
    mesh_name = ("custom" if mesh is not None
                 else "2x16x16" if multi_pod else "16x16")
    rec = {"arch": arch, "cell": cell_name, "mesh": mesh_name,
           "baseline": baseline}
    if skip:
        rec["skipped"] = skip
        print(f"[dryrun] SKIP {arch} × {cell_name} × {mesh_name}: {skip}")
    else:
        mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
        t0 = time.perf_counter()
        cfg, lowered, compiled, compile_s = lower_cell(arch, cell, mesh,
                                                       baseline=baseline,
                                                       reduced=reduced)
        rec.update(analyze(lowered, compiled, mesh))
        rec["microbatches"] = 4 if (cell.kind == "train" and arch in BIG_TRAIN) else 1
        rec["compile_s"] = compile_s
        rec["total_s"] = time.perf_counter() - t0
        mem = rec["memory"]
        print(f"[dryrun] OK {arch} × {cell_name} × {mesh_name}"
              f"{' [baseline]' if baseline else ''}: "
              f"flops={rec['flops']:.3e} coll={rec['collectives']['total']:.3e}B "
              f"args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"compile={compile_s:.0f}s")
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "_baseline" if baseline else ""
    fname = out_dir / f"{arch}_{cell_name}_{mesh_name}{suffix}.json"
    fname.write_text(json.dumps(rec, indent=1))
    return rec


# ---------------------------------------------------------------------------
# Depth probes: XLA counts a scan body once regardless of trip count, so the
# raw cost_analysis underestimates layer-stack costs.  Compiling depth-1 and
# depth-2 variants gives exact per-layer deltas; benchmarks/roofline.py
# extrapolates  total = base + Σ n_seg · Δ_seg  (see EXPERIMENTS.md §Roofline
# methodology).
# ---------------------------------------------------------------------------
def probe_plan(arch: str) -> list[tuple[str, dict]]:
    cfg = get_config(arch)
    fam = cfg.family
    ft = cfg.ttd.first_tt_block
    if fam == "encdec":
        return [("e1d1", {"n_enc_layers": 1, "n_layers": 1}),
                ("e2d1", {"n_enc_layers": 2, "n_layers": 1}),
                ("e1d2", {"n_enc_layers": 1, "n_layers": 2})]
    if fam == "griffin":
        return [("g1", {"n_layers": 3}), ("g2", {"n_layers": 6}),
                ("g1r1", {"n_layers": 4})]
    if ft > 0:  # two-segment transformers (paper's partial-TT recipe)
        return [("d1", {"n_layers": 1, "_ft": 1}), ("d2", {"n_layers": 2, "_ft": 2}),
                ("t1", {"n_layers": 1, "_ft": 0}), ("t2", {"n_layers": 2, "_ft": 0})]
    return [("L1", {"n_layers": 1}), ("L2", {"n_layers": 2})]


def probe_cell(arch: str, cell_name: str, out_dir: Path) -> dict:
    cell = shape_cell(cell_name)
    if cell_skip_reason(arch, cell):
        return {}
    mesh = make_production_mesh(multi_pod=False)
    rec = {"arch": arch, "cell": cell_name, "probes": {}}
    for tag, mods in probe_plan(arch):
        mods = dict(mods)
        ft = mods.pop("_ft", None)
        base_cfg = arch_cell_config(arch, cell)
        cfg = base_cfg.replace(**mods)
        if ft is not None:
            cfg = cfg.replace(ttd=base_cfg.ttd.__class__(
                **{**base_cfg.ttd.__dict__, "first_tt_block": ft}))
        model = build_model(cfg)
        batch = input_specs(cfg, cell)
        # lower exactly like lower_cell but with the mutated cfg
        lowered, compiled = _lower_with_cfg(cfg, model, cell, mesh, arch)
        a = analyze(lowered, compiled, mesh)
        rec["probes"][tag] = {"flops": a["flops"], "bytes": a["bytes_accessed"],
                              "coll": a["collectives"]["total"],
                              "coll_by": {k: a["collectives"][k] for k in _COLL_OPS}}
        print(f"[probe] {arch} × {cell_name} × {tag}: flops={a['flops']:.3e}")
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{arch}_{cell_name}_16x16_probes.json").write_text(json.dumps(rec, indent=1))
    return rec


def _lower_with_cfg(cfg, model, cell, mesh, arch):
    """Shared lowering used by probes (mirrors lower_cell's three kinds)."""
    batch = input_specs(cfg, cell)
    with jax.set_mesh(mesh):
        if cell.kind == "train":
            opt = "adafactor" if arch in BIG_TRAIN else "adamw"
            mb = 4 if arch in BIG_TRAIN else 1
            tc = TrainConfig(global_batch=cell.global_batch, seq_len=cell.seq_len,
                             optimizer=opt, remat="full", microbatches=mb)
            step = build_train_step(model, tc)
            specs = state_pspecs(model, tc, mesh)
            state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                    is_leaf=lambda x: isinstance(x, P))
            bspec = jax.tree.map(
                lambda x: NamedSharding(mesh, batch_pspec(mesh, len(x.shape) - 1)), batch)
            if "positions" in batch:
                bspec["positions"] = NamedSharding(mesh, P(None, "data", None))

            def _make_state(key):
                params = model.init(key)
                return TrainState(params=params, opt=init_optimizer(tc.optimizer, params),
                                  step=jnp.zeros((), jnp.int32))

            state_shapes = jax.eval_shape(_make_state, jax.random.PRNGKey(0))
            jitted = jax.jit(step, in_shardings=(state_sh, bspec),
                             out_shardings=(state_sh, None))
            lowered = jitted.lower(state_shapes, batch)
        elif cell.kind == "prefill":
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            psh = param_shardings(pshapes, mesh, fsdp=False)
            bspec = jax.tree.map(
                lambda x: NamedSharding(mesh, batch_pspec(mesh, len(x.shape) - 1)), batch)
            if "positions" in batch:
                bspec["positions"] = NamedSharding(mesh, P(None, "data", None))
            jitted = jax.jit(lambda p, b: model.prefill(p, b, max_len=cell.seq_len),
                             in_shardings=(psh, bspec))
            lowered = jitted.lower(pshapes, batch)
        else:
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            psh = param_shardings(pshapes, mesh, fsdp=False)
            cshapes = jax.eval_shape(
                lambda: model.init_cache(cell.global_batch, cell.seq_len, jnp.bfloat16))
            csh = cache_shardings(cshapes, mesh)
            bax = "data" if cell.global_batch % mesh.shape["data"] == 0 else None
            bspec = {"tokens": NamedSharding(mesh, P(bax, None))}
            if "positions" in batch:
                bspec["positions"] = NamedSharding(mesh, P(None, bax, None))
            jitted = jax.jit(lambda p, c, b, pos: model.decode_step(p, c, b, pos),
                             in_shardings=(psh, csh, bspec, None))
            lowered = jitted.lower(pshapes, cshapes, batch,
                                   jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, lowered.compile()


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="arch id (default: all assigned)")
    ap.add_argument("--shape", default=None, help="shape cell (default: all four)")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--baseline", action="store_true",
                    help="lower the non-TTD baseline instead of the paper config")
    ap.add_argument("--probe", action="store_true",
                    help="run depth-delta probes (single-pod) instead of full cells")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ALL_ARCHS)
    cells = [args.shape] if args.shape else ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)
    failures = []
    for arch in archs:
        for cell in cells:
            if args.probe:
                try:
                    probe_cell(arch, cell, out_dir)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, cell, "probe", repr(e)))
                    print(f"[dryrun] FAIL probe {arch} × {cell}: {e!r}")
                continue
            for mp in meshes:
                try:
                    run_cell(arch, cell, mp, out_dir, baseline=args.baseline)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, cell, mp, repr(e)))
                    print(f"[dryrun] FAIL {arch} × {cell} × mp={mp}: {e!r}")
    if failures:
        print(f"[dryrun] {len(failures)} failures")
        sys.exit(1)
    print("[dryrun] all requested cells compiled")


if __name__ == "__main__":
    main()
