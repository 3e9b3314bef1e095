"""The knee of an open-loop cell: its traffic at several fixed rates.

    python3 bench/sweep.py --workload chatglm3-tt.chat --rates 0.5,1,2 --seconds 40

One process, one seed; for each rate the cell's traffic mix with that
``rate_rps`` runs for ``--seconds`` and the line printed gives the time to
first token of the first and the second half of the window.  The knee is the
highest rate at which the second half is not slower than the first (no
backlog grows).  A cell's rate is set once from such a sweep, at about four
fifths of the knee, and PERF.md records the sweep.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("the sweep reads the chip; no TPU found", file=sys.stderr)
        return 3
    import harness
    harness.use_cache(ROOT)
    from stats import nearest_rank, ttfts
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    for rate in (float(r) for r in args.rates.split(",")):
        captured = {}
        res = harness.run_cell(spec, args.workload, args.seed, args.seconds, False,
                               root=ROOT, t_start=time.perf_counter(),
                               mix_override={"rate_rps": rate}, compare=False,
                               keep_records=captured)
        recs = captured["records"]
        mid = captured["t0"] + args.seconds / 2
        halves = [[r for r in recs if (r.due < mid) == first] for first in (True, False)]
        print(json.dumps({
            "rate_rps": rate, "metrics": res["metrics"], "failed": res["failed"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "ttft_p50_ms_first_half": 1e3 * nearest_rank(ttfts(halves[0]), 50),
            "ttft_p50_ms_second_half": 1e3 * nearest_rank(ttfts(halves[1]), 50),
            "ttft_p90_ms_second_half": 1e3 * nearest_rank(ttfts(halves[1]), 90)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
