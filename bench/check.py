"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests is drawn from
the seed (the longest always among them) until it holds ``min_tokens``
served tokens.  The plain reference of the configuration runs once over
each sampled prompt followed by its served tokens, and for every served
token reads how far its reference logit lies below the reference's best at
that position.  The number compared is the widest such gap.  A correct
greedy server only loses to the reference where two logits lie within its
rounding of each other; a server that drops a KV block, skips a layer or
alters a token loses by far more.

The control puts the reference, computed in float8 (``quant="fp8"``), in the
program's place: at the same positions it reads the gap of the token the
float8 computation ranks first.
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import model as bench_model
from traffic import longest_sequence, padded_length


def load_reference(config_path: Path, cj: dict):
    return bench_model.load_module(Path(config_path).parent / cj["reference"],
                                   "reference")


def draw_sample(records, seed: int, min_tokens: int, max_requests: int):
    """Finished requests to compare: the longest, then others in a seeded
    order until ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in records if r.finished and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens), r.req.idx))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    sample, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(sample) >= max_requests:
            break
        sample.append(rest[i])
        n += len(rest[i].tokens)
    return sample


def reference(config_path: Path, mix: dict, quant=None) -> "Reference":
    """The configuration's reference for a cell's sizes; built once per
    process for each (configuration, sizes, precision)."""
    return _reference(str(config_path), padded_length(longest_sequence(mix)),
                      int(mix["output"]["max"]), quant)


@functools.lru_cache(maxsize=4)
def _reference(config_path: str, s_pad: int, k: int, quant):
    cj = bench_model.load_config(config_path)
    return Reference(load_reference(Path(config_path), cj), cj, s_pad, k, quant)


class Reference:
    """The configuration's plain reference, compiled once for a cell's
    longest sequence and largest output."""

    def __init__(self, ref_mod, cj: dict, s_pad: int, k: int, quant=None):
        self.s_pad, self.k = s_pad, k
        tt = bench_model.tt_roles(cj)
        model = cj["model"]

        def fwd(params, tokens, pick):
            return ref_mod.forward(params, model, tt, tokens, pick, quant=quant)

        self._fwd = jax.jit(fwd)

    def logits(self, params, prompt: list[int], served: list[int]):
        """Reference logits (K, V) at the positions that predicted each
        served token (rows past ``len(served)`` repeat the first)."""
        seq = list(prompt) + list(served[:-1])
        tokens = np.zeros(self.s_pad, np.int32)
        tokens[:len(seq)] = seq
        pick = np.full(self.k, len(prompt) - 1, np.int32)
        pick[:len(served)] = len(prompt) - 1 + np.arange(len(served))
        return self._fwd(params, jnp.asarray(tokens), jnp.asarray(pick))


def served_gaps(ref_logits, served: list[int]) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    lg = np.asarray(ref_logits, np.float64)[:len(served)]
    return lg.max(-1) - lg[np.arange(len(served)), np.asarray(served)]


def control_gaps(ref_logits, ctrl_logits, n: int) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of the
    token the control ranks first."""
    lg = np.asarray(ref_logits, np.float64)[:n]
    pick = np.asarray(ctrl_logits)[:n].argmax(-1)
    return lg.max(-1) - lg[np.arange(n), pick]


def compare(params, ref: Reference, sample, control: Reference | None = None):
    """{"served_gap": widest gap, "tokens": n compared, "requests": n, and
    with a control "control_gap"}."""
    gaps, cgaps, n = [], [], 0
    for rec in sample:
        lg = ref.logits(params, rec.req.prompt, rec.tokens)
        gaps.append(served_gaps(lg, rec.tokens))
        if control is not None:
            cl = control.logits(params, rec.req.prompt, rec.tokens)
            cgaps.append(control_gaps(lg, cl, len(rec.tokens)))
        n += len(rec.tokens)
    out = {"served_gap": float(np.max(np.concatenate(gaps))) if gaps else None,
           "tokens": n, "requests": len(sample)}
    if control is not None:
        out["control_gap"] = float(np.max(np.concatenate(cgaps)))
    return out
