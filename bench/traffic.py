"""The one traffic generator: a mix file of parameters -> seeded requests.

A traffic mix is a JSON file under ``bench/traffic/``.  It states the loop
(``open``: Poisson arrivals at ``rate_rps``, the one loop the harness
drives), the length distributions of prompts and outputs, the serving
geometry, and how many served tokens the correctness check compares.

Every seed gets the same work in the same order.  The lengths are a fixed
set, the quantiles of each distribution at ``(i + 0.5) / n``; the arrival
gaps of an open loop are a fixed set of exponential quantiles scaled so that
the ``n`` counted arrivals span the window exactly.  The three sets are
shuffled once, by a permutation that no seed changes: a window holds few
requests, and which long prompt lands next to which short gap moves the
median first-token time by more than the noise of a run.  The seed draws
the prompt tokens (and the harness the weights), so runs with different
seeds differ in content, not in the work or its timing.  (The seeded
``repro.traffic.workload`` generator of the program draws lengths from
bucket mixtures instead; this copy is what the benchmark measures with, so
that later changes to the program cannot change the yardstick.)
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist

import numpy as np


def load_mix(path: str | Path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["loop"] != "open":
        raise ValueError(f"{path}: loop must be 'open'")
    return mix


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles at ``(i + 0.5) / n`` of a length distribution,
    rounded and clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


@dataclass
class Request:
    """One request as the client sends it."""

    idx: int
    prompt: list[int] = field(repr=False)
    max_tokens: int
    due: float = 0.0        # open loop: seconds after the window opens
    counted: bool = True    # due inside the window (open loop)


# seeds the one shuffle of lengths and gaps that every run shares
ORDER_SEED = 20250131


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def open_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                  tail_s: float) -> list[Request]:
    """Requests of an open loop, sorted by due time.

    The first ``round(rate * seconds)`` are due inside the window and are
    counted; after them the same process goes on for ``tail_s`` seconds, so
    that the counted requests are served under the same load to their end.
    """
    rate = mix["rate_rps"]
    n = max(1, round(rate * seconds))
    n_tail = max(1, round(rate * tail_s))
    out: list[Request] = []
    t = 0.0
    for part, (count, span) in enumerate(((n, seconds), (n_tail, tail_s))):
        order = _rng(ORDER_SEED, part)  # the same for every seed
        tokens = _rng(seed, part)
        u = (np.arange(count) + 0.5) / count
        gaps = -np.log1p(-u)
        gaps = order.permutation(gaps * span / gaps.sum())
        plens = order.permutation(quantile_lengths(mix["prompt"], count))
        olens = order.permutation(quantile_lengths(mix["output"], count))
        for i in range(count):
            t += float(gaps[i])
            out.append(Request(
                idx=len(out), max_tokens=int(olens[i]), due=t,
                counted=part == 0,
                prompt=[int(x) for x in tokens.integers(1, vocab, int(plens[i]))]))
    return out


def warmup_requests(mix: dict, vocab: int) -> list[Request]:
    """Two requests that send every program the window will run through the
    engine: two prefill chunks each, and enough decode ticks to chain
    dispatch-ahead ticks."""
    chunk = mix["engine"]["prefill_chunk"]
    rng = _rng(0, 999)
    return [Request(idx=i, max_tokens=8,
                    prompt=[int(x) for x in rng.integers(1, vocab, chunk + 3 + i)])
            for i in range(2)]


def longest_sequence(mix: dict) -> int:
    return int(mix["prompt"]["max"] + mix["output"]["max"])


def padded_length(n: int, multiple: int = 512) -> int:
    return int(math.ceil(n / multiple) * multiple)
