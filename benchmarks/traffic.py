"""Realistic-traffic benchmark: async front-end under seeded open-loop load.

Replays deterministic Poisson and bursty arrival schedules
(``repro.traffic``) against the asyncio serving front-end
(``repro.serve.frontend``) for three model families — dense paged-attention
(tight block pool, so bursts preempt), a recurrent-state family (rwkv), and
a TT+int4-compressed model — and writes one row per (family, scenario) to
``BENCH_traffic.json``: p50/p95/p99 TTFT and inter-token latency from the
obs registry, goodput (SLO-attained tokens/sec), and preemption / client
cancellation / deadline-miss counts.  CPU wall-time on the reduced configs —
a structural comparison of scheduling under load, not TPU performance.

    PYTHONPATH=src python benchmarks/traffic.py
    PYTHONPATH=src python benchmarks/traffic.py --smoke --check-schema
    PYTHONPATH=src python benchmarks/traffic.py --check-schema BENCH_traffic.json
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

from repro.configs import get_config

try:
    from .compressed_serve import variant_cfgs
except ImportError:  # standalone `python benchmarks/traffic.py`
    from compressed_serve import variant_cfgs

FAMILIES = ("dense/paged", "rwkv", "tt_int4")


def family_setup(family: str):
    """(arch, model, params, engine kwargs) for one benchmark family."""
    import jax

    from repro.models import build_model

    if family == "tt_int4":
        from repro.core.compress import compress_model

        dense_cfg, target = variant_cfgs("tinyllama-1.1b", "tt_int4")
        dense_model = build_model(dense_cfg)
        params = compress_model(dense_model.init(jax.random.PRNGKey(0)),
                                dense_cfg, target)
        return ("tinyllama-1.1b", build_model(target), params,
                dict(slots=2, max_len=96, block_size=8, prefill_batch=2,
                     prefill_chunk=8))
    if family == "rwkv":
        cfg = get_config("rwkv6-7b", reduced=True).replace(
            compute_dtype="float32", param_dtype="float32")
        model = build_model(cfg)
        return ("rwkv6-7b", model, model.init(jax.random.PRNGKey(0)),
                dict(slots=4, max_len=96, prefill_batch=2, prefill_chunk=8))
    assert family == "dense/paged", family
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    # deliberately tight block pool: bursty arrivals overcommit it, so the
    # preemption path shows up in the preempts column
    return ("tinyllama-1.1b", model, model.init(jax.random.PRNGKey(0)),
            dict(slots=4, max_len=96, backend="paged", block_size=8,
                 num_blocks=12, prefill_batch=2, prefill_chunk=8))


def scenario_specs(vocab: int, n_requests: int, deadline_s: float | None):
    """The seeded arrival scenarios every family is measured under."""
    from repro.traffic import WorkloadSpec

    common = dict(n_requests=n_requests,
                  prompt_len_buckets=(6, 16, 40),
                  prompt_len_weights=(0.5, 0.3, 0.2),
                  out_tokens_buckets=(4, 12, 24),
                  out_tokens_weights=(0.5, 0.3, 0.2),
                  vocab=vocab, ttft_slo_s=0.35, deadline_s=deadline_s,
                  cancel_prob=0.25, cancel_window_s=(0.005, 0.08))
    return {
        "poisson": WorkloadSpec(arrival="poisson", rate_rps=6.0, seed=7,
                                **common),
        "bursty": WorkloadSpec(arrival="bursty", rate_rps=8.0, burst_size=4,
                               seed=11, **common),
    }


def _warmup(model, params, kwargs) -> None:
    """Compile every program shape untimed (steps memoize per config)."""
    import jax.numpy as jnp

    from repro.serve import steps
    from repro.serve.engine import Engine

    eng = Engine(model, params, obs=False, **kwargs)
    for i, plen in enumerate((5, 20)):  # single- and multi-chunk prefill
        eng.submit([1 + (i + j) % 7 for j in range(plen)], max_tokens=4)
    eng.run()
    # the async pump's device-side argmax is its own jitted program
    steps.greedy_tokens(jnp.zeros((kwargs["slots"], model.cfg.vocab_size),
                                  jnp.float32))


def run(report=print, *, families=FAMILIES, n_requests: int = 12,
        time_scale: float = 1.0, deadline_s: float | None = 20.0,
        out_path: str = "BENCH_traffic.json"):
    from repro.obs import ObsConfig, Observer
    from repro.serve import AsyncEngine
    from repro.serve.engine import Engine
    from repro.traffic import drive, make_workload, traffic_row

    jsonl = os.environ.get("REPRO_OBS_JSONL") or None
    rows = []
    report(f"== traffic: {len(families)} families x 2 arrival scenarios, "
           f"{n_requests} requests each (time_scale={time_scale})")
    for family in families:
        arch, model, params, kwargs = family_setup(family)
        _warmup(model, params, kwargs)
        specs = scenario_specs(model.cfg.vocab_size, n_requests, deadline_s)
        for scenario, spec in specs.items():
            requests = make_workload(spec)
            # fresh per-scenario observer; all scenarios may append to one
            # JSONL (trace seq numbers are process-wide, so the merged log
            # still validates)
            obs = Observer(ObsConfig(jsonl_path=jsonl))
            frontend = AsyncEngine(engine=Engine(model, params, obs=obs,
                                                 **kwargs))
            result = drive(frontend, requests, time_scale=time_scale)
            obs.close()
            row = traffic_row(
                result=result, registry=obs.registry, family=family,
                arch=arch, scenario=scenario, workload=spec.to_dict(),
                ahead_tick_fraction=(
                    obs.registry.get("serve_ahead_ticks_total").value
                    / max(1.0, obs.registry.get("serve_decode_ticks_total").value)))
            rows.append(row)
            report(f"   {family:12s} {scenario:8s} "
                   f"goodput {row['goodput_tok_per_s']:7.1f} tok/s "
                   f"(of {row['tok_per_s']:7.1f})  "
                   f"ttft p50 {row['ttft_s']['p50']*1e3:7.1f}ms "
                   f"p99 {row['ttft_s']['p99']*1e3:7.1f}ms  "
                   f"preempts {row['preempts']:2d} cancels {row['cancels']:2d}"
                   f" misses {row['n_deadline_missed']:2d}")
    rec = {
        "scenarios": {"names": sorted({r["scenario"] for r in rows}),
                      "n_requests": n_requests, "time_scale": time_scale,
                      "deadline_s": deadline_s},
        "note": "CPU wall-clock on the reduced configs: open-loop seeded "
                "arrivals through the asyncio front-end (dispatch-ahead "
                "double buffering) — scheduling structure under load, not "
                "TPU kernel performance.",
        "rows": rows,
    }
    Path(out_path).write_text(json.dumps(rec, indent=1))
    report(f"wrote {out_path}")
    return rows


# ---------------------------------------------------------------------------
# CI modes
# ---------------------------------------------------------------------------
def smoke(report=print, out_path: str = "BENCH_traffic.json"):
    """Tiny full-matrix run: every family and scenario, 4 requests each.

    No deadlines (CI machines jitter too much for miss counts to be stable)
    and a compressed clock; the output still satisfies the full schema, so
    ``--smoke --check-schema`` validates what it just wrote.
    """
    return run(report=report, n_requests=4, time_scale=0.5, deadline_s=None,
               out_path=out_path)


def check_schema(path, report=print):
    """Validate a BENCH_traffic.json against the acceptance shape.

    Delegates to the shared BENCH schema table (``repro.analyze.bench``) —
    the same validation ``python -m repro.analyze --bench`` runs in CI.
    """
    from repro.analyze.bench import check_file

    errors = check_file("traffic", Path(path))
    assert not errors, "; ".join(errors)
    rows = json.loads(Path(path).read_text())["rows"]
    report(f"schema OK: {path} ({len(rows)} rows, "
           f"{len({r['family'] for r in rows})} families x "
           f"{len({r['scenario'] for r in rows})} scenarios)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI: tiny full-matrix run (all families/scenarios, "
                         "4 requests)")
    ap.add_argument("--check-schema", nargs="?", const="", metavar="PATH",
                    help="CI: schema-validate a results file (no PATH: "
                         "whatever --out points at; combines with --smoke)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--out", default="BENCH_traffic.json")
    args = ap.parse_args(argv)
    if args.smoke:
        smoke(out_path=args.out)
    elif args.check_schema is None:
        run(n_requests=args.requests, time_scale=args.time_scale,
            out_path=args.out)
    if args.check_schema is not None:
        check_schema(args.check_schema or args.out)


if __name__ == "__main__":
    main()
