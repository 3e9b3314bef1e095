"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload chatglm3-tt.chat --seeds 1-12 --seconds 15

Runs the cell once per seed in one process (the programs compile or load
once), each with a window of ``--seconds`` at the cell's own load, and on
the same sample reads both the program's widest gap and the float8
control's.  One JSON line per seed; the limit in ``bench/limits/<cell>.json``
is set between the largest program reading and the smallest control
reading, as PERF.md records.  The control is judged by the same verdict as
the program, in its place (``control_correct``, which has to be false).
The benchmark's own runs never run the control.
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


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,8")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="an open loop's rate, before it is fixed in the mix")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibration reads the chip; no TPU found", file=sys.stderr)
        return 3
    import harness
    harness.use_cache(ROOT)
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    t0 = T_START
    for seed in seeds(args.seeds):
        res = harness.run_cell(
            spec, args.workload, seed, args.seconds, False, root=ROOT,
            t_start=t0, with_control=True,
            mix_override={"rate_rps": args.rate} if args.rate else None)
        c = res["checks"]
        print(json.dumps({"seed": seed, "served_gap": c["served_gap"]["value"],
                          "control_gap": c["control_gap"]["value"],
                          "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "tokens": c["tokens_compared"]["value"],
                          "failed": res["failed"], "metrics": res["metrics"],
                          "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
              flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
