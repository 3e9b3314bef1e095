"""chip_smoke.py at CPU size: its phases over the Pallas interpreter, and
its refusal to run without a TPU.

The script itself only runs on a TPU; these tests drive its phase functions
on reduced chatglm3-6b (2 layers, width 64) with ``pallas-interpret`` in
place of the compiled kernels, so a broken phase is caught before chip time.
"""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.launch.serve import build

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reduced():
    cfg, model, params = build("chatglm3-6b", reduced=True)
    rng = np.random.default_rng(0)
    return cfg, model, params, rng


def _serve(smoke, reduced, kernel_backend):
    cfg, model, params, rng = reduced
    prompts = [smoke.prompt(rng, n, cfg.vocab_size) for n in (5, 12, 20, 33)]
    jax.clear_caches()  # the dispatch counters count traces: retrace everything
    dispatch.reset_dispatch_metrics()
    engine, _, _ = smoke.serve(model, params, prompts, max_tokens=3,
                               kernel_backend=kernel_backend, max_len=64,
                               prefill_chunk=16)
    return engine


def test_smoke_refuses_to_run_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "[device] platform=cpu" in out and '"ok"' not in out


def test_smoke_phases_at_cpu_size(smoke, reduced):
    cfg, _, params, rng = reduced
    engine = _serve(smoke, reduced, "pallas-interpret")
    tt_roles = sorted({r for r, _ in cfg.ttd.overrides})
    roles = smoke.check_backends(tt_roles, "pallas-interpret")
    assert set(tt_roles) | set(smoke.ATTENTION_ROLES) <= set(roles)
    errs = smoke.check_logits(engine.session, params,
                              smoke.prompt(rng, 12, cfg.vocab_size),
                              kernel_backend="pallas-interpret")
    assert errs["prefill"] < smoke.LOGITS_RTOL
    assert errs["decode"] < smoke.LOGITS_RTOL
    assert errs["zeroed_block_control"] > smoke.LOGITS_RTOL


def test_smoke_backend_check_catches_the_reference(smoke, reduced):
    """A run the reference served must fail the kernel check."""
    cfg = reduced[0]
    _serve(smoke, reduced, "ref")
    with pytest.raises(SystemExit, match="not served by"):
        smoke.check_backends(sorted({r for r, _ in cfg.ttd.overrides}),
                             "pallas-interpret")
