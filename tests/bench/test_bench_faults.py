"""The comparison that decides ``correct`` fails what it must fail.

At the tiny CPU size: the float8 control reads a gap far above the
program's, and a run with the timed path broken underneath (each fault a
served cell can have on one chip) comes out not correct.
"""
import json
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path[:0] = [str(ROOT / "bench")]

import harness  # noqa: E402
from repro.models.sessions import PagedKVSession  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402
from repro.serve import steps  # noqa: E402


def spec():
    s = json.loads((ROOT / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "tiny-tt", "file": "tests/bench/fixtures/tiny-tt.json"})
    s["workloads"].append({"name": "tiny.open", "config": "tiny-tt",
                           "traffic": "tiny", "chips": 1})
    return s


def run(**kw):
    # an open loop serves every counted request to its end, so the sample
    # compared does not depend on how fast the machine is
    return harness.run_cell(spec(), "tiny.open", 1, 2.0, False,
                            root=ROOT, t_start=time.perf_counter(),
                            kernel_backend="pallas-interpret",
                            expect_backend="pallas-interpret",
                            traffic_dir=FIX, limits_dir=FIX, **kw)


def test_control_fails_the_limit_the_program_passes():
    res = run(with_control=True)
    c = res["checks"]
    limit = c["served_gap"]["limit"]
    assert res["correct"] is True
    assert c["served_gap"]["value"] <= limit < c["control_gap"]["value"]
    # the control in the program's place, through the same verdict
    assert res["control_correct"] is False
    assert harness.verdict(dict(c, served_gap=c["control_gap"])) is False


def _state_unchanged(monkeypatch):
    real = PagedKVSession.decode_step

    def decode_step(self, params, state, tokens, positions):
        logits, _ = real(self, params, state, tokens, positions)
        return logits, state  # the tick's K/V are never written

    monkeypatch.setattr(PagedKVSession, "decode_step", decode_step)


def _half_batch(monkeypatch):
    real = PagedKVSession.decode_step

    def decode_step(self, params, state, tokens, positions):
        # the first half of the slots (where the engine seats requests first)
        # is computed as if idle
        half = jnp.arange(positions.shape[0]) < positions.shape[0] // 2
        return real(self, params, state, tokens, jnp.where(half, -1, positions))

    monkeypatch.setattr(PagedKVSession, "decode_step", decode_step)


def _token_altered(monkeypatch):
    real_greedy, real_sample = steps.greedy_tokens, engine_mod.Engine._sample
    vocab = 512

    monkeypatch.setattr(steps, "greedy_tokens",
                        lambda logits: (real_greedy(logits) + 1) % vocab)
    monkeypatch.setattr(engine_mod.Engine, "_sample",
                        lambda self, logits: (real_sample(self, logits) + 1) % vocab)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _token_altered],
                         ids=["state-unchanged", "half-batch", "token-altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(steps, "_STEP_CACHE", {})  # trace the broken step anew
    fault(monkeypatch)
    res = run()
    assert res["correct"] is False
    c = res["checks"]
    assert c["served_gap"]["value"] > c["served_gap"]["limit"]
