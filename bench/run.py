"""The benchmark: one cell of BENCHMARK.json, run once on the chip.

    python3 bench/run.py --workload chatglm3-tt.chat --seed 7 --seconds 30 --trace 0

From the root of a checkout.  The cell's configuration is built at its
published widths with weights drawn from ``--seed`` on the device, served
through the program's async front-end (``AsyncEngine`` over ``Engine``, the
paged bf16 KV cache and the Pallas kernels), and loaded by the cell's
traffic mix for ``--seconds``.  Once the window has closed, a seeded sample
of the served requests is compared with the configuration's plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the middle of the window), ``device`` and, last, ``checks``: each
number compared with its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root of
the checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    spec_path = ROOT / "BENCHMARK.json"
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program next to the benchmark (looked for {ROOT / 'src' / 'repro'})",
              file=sys.stderr)
        return 2
    with open(spec_path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    # the cache lives in the checkout: a path that never moves, shared by no
    # other checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    devices = jax.devices()
    need = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"this cell needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    import harness
    harness.use_cache(ROOT)
    result = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                              bool(args.trace), root=ROOT, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
