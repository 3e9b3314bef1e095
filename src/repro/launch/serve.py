"""Serving launcher: the unified session engine over a registry model.

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --requests 8 --max-tokens 12

Without ``--reduced`` the model is built at its published widths and depth,
in the dtypes its config declares, with random weights drawn from a seed
(:func:`build`); ``chip_smoke.py`` drives the same builder.  Any family
serves: the engine picks the architecture's default state backend (paged
block pools, per-slot rings for SWA, recurrent state, or encoder-context +
paged self-attention for enc-dec) — override with ``--backend``.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ALL_ARCHS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serve.engine import Engine


def build(arch: str, *, reduced: bool = False, seed: int = 0):
    """(config, model, params) for ``arch`` with random weights from ``seed``.

    The params are drawn inside one jitted program, so each layer stack is
    produced directly in the config's ``param_dtype``: no float32 copy of a
    stack is ever resident on the device.
    """
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return cfg, model, params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list(ALL_ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="the CPU-sized config (widths cut to 64)")
    ap.add_argument("--backend", default=None,
                    help="state backend (default: family's preferred)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg, model, params = build(args.arch, reduced=args.reduced)
    engine = Engine(model, params, slots=args.slots, max_len=args.max_len,
                    backend=args.backend, prefill_chunk=args.prefill_chunk)
    print(f"{cfg.name}: serving through the {engine.session.backend!r} backend")
    for i in range(args.requests):
        engine.submit([1 + i, 2, 3] + list(range(4, 4 + i % 5)),
                      max_tokens=args.max_tokens)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests / {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
