"""The benchmark end to end on the CPU at a tiny size, and its refusals.

The harness runs a tiny configuration through the program's async
front-end with the Pallas kernels in interpret mode; the command line, which
the chip runs, refuses a machine without a TPU and a checkout without the
program.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path[:0] = [str(ROOT / "bench")]

import harness  # noqa: E402
import model as bench_model  # noqa: E402
import peaks  # noqa: E402
import serve as drivers  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402


def tiny_spec():
    """BENCHMARK.json with a tiny cell added (the fixtures' config and mix)
    that reports the chat cell's metrics."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-tt",
                            "file": "tests/bench/fixtures/tiny-tt.json"})
    spec["workloads"].append(
        {"name": "tiny.open", "config": "tiny-tt", "traffic": "tiny", "chips": 1})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.open")
    return spec


def moe_spec():
    """``tiny_spec()`` with a configuration of another shape added by files
    alone, all under ``tests/bench/fixtures``: a mixture of experts with no
    TT layers, its own reference (with a leaf rule), its own operation
    counts, and a float32 mix."""
    spec = tiny_spec()
    spec["configs"].append({"name": "tiny-moe",
                            "file": "tests/bench/fixtures/tiny-moe.json"})
    spec["workloads"].append({"name": "tiny-moe.open", "config": "tiny-moe",
                              "traffic": "tiny-f32", "chips": 1})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-moe.open")
    return spec


def run_tiny(cell, seconds=2.0, trace=False, spec=None, **kw):
    kw = dict(dict(kernel_backend="pallas-interpret",
                   expect_backend="pallas-interpret", traffic_dir=FIX,
                   limits_dir=FIX), **kw)
    return harness.run_cell(spec or tiny_spec(), cell, 2**33 + 5, seconds, trace,
                            root=ROOT, t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.open", {"ttft_p50_ms", "itl_p95_ms", "setup_s"}),
])
def test_run_prints_a_result_line_that_parses(cell, metrics, capsys):
    res = run_tiny(cell)
    line = json.loads(json.dumps(res))  # the line run.py prints
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    c = line["checks"]
    assert c["served_gap"]["value"] <= c["served_gap"]["limit"]
    assert c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
    # the numbers compared are the last lines on standard error
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-len(c):] == [f"check {k} {v['value']} limit {v['limit']}"
                             for k, v in c.items()]


def test_a_configuration_of_another_shape_enters_by_its_files_alone(monkeypatch):
    cj = json.loads((FIX / "tiny-moe.json").read_text())
    assert cj["model"]["family"] == "moe" and "ttd" not in cj["model"]
    # every file the cell reads lies outside bench/
    for f in (FIX / "tiny-moe.json", FIX / cj["reference"], FIX / cj["ops"],
              FIX / "tiny-f32.json", FIX / "tiny-moe.open.json"):
        assert f.is_file() and (ROOT / "bench") not in f.resolve().parents
    seen = {}
    real_served = bench_model.served_config

    def served(cj, **kw):
        seen["cfg"] = real_served(cj, **kw)
        return seen["cfg"]

    monkeypatch.setattr(bench_model, "served_config", served)
    res = run_tiny("tiny-moe.open", spec=moe_spec(), with_control=True)
    c = res["checks"]
    assert res["correct"] is True, c
    assert res["failed"] == 0 and res["attempted"] > 0
    assert c["roles_not_pallas"]["value"] == 0
    assert c["served_gap"]["value"] <= c["served_gap"]["limit"] < c["control_gap"]["value"]
    assert res["control_correct"] is False
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    cfg = seen["cfg"]
    assert (cfg.family, cfg.n_experts, cfg.experts_per_token, cfg.d_ff_expert) == \
        ("moe", 8, 2, 32)
    assert not cfg.ttd.enabled


def test_run_without_a_limit_is_not_correct(tmp_path):
    res = run_tiny("tiny.open", limits_dir=tmp_path)
    assert res["correct"] is False
    assert res["checks"]["served_gap"]["limit"] is None


def test_run_whose_hooks_record_nothing_is_not_correct(monkeypatch):
    # the program's decode path no longer passes through the benchmark's
    # hook, as after a restructuring of Engine
    monkeypatch.setattr(drivers.RecordingEngine, "_decode_dispatch",
                        Engine._decode_dispatch)
    res = run_tiny("tiny.open")
    assert res["checks"]["hooks_silent"]["value"] == 1
    assert res["correct"] is False


class _BlankTrace:
    """A profiler stand-in whose trace holds no device operation."""

    def __init__(self, seconds):
        pass

    def schedule(self, t0):
        pass

    async def run(self):
        pass

    def reduce(self, engine, geo, run):
        return {"window_s": 1.0, "busy_s": 0.5, "execs": {}, "kernel_events": [],
                "ops": [], "breakdown": {"device_ops": [], "idle_gaps": []}}


def test_traced_run_whose_per_layer_metrics_read_nothing_is_not_correct(monkeypatch):
    monkeypatch.setattr(harness, "_Tracer", _BlankTrace)
    monkeypatch.setattr(harness, "peaks_for", lambda kind: peaks.PEAKS["TPU v5 lite"])
    res = run_tiny("tiny.open", trace=True)
    listed = {m["name"] for m in harness.cell_metrics(tiny_spec(), "tiny.open",
                                                      "per_layer")}
    unread = listed - set(res["metrics"])
    assert unread  # the device metrics found no device operation
    assert res["checks"]["per_layer_unread"]["value"] == len(unread)
    assert res["correct"] is False


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chatglm3-tt.chat",
         "--seed", "3", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_machine_without_a_tpu():
    out = _cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
