"""The readers of the program's own events and spans, on a synthetic
traced run, and on a tiny traced run of the program on the CPU."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import peaks  # noqa: E402
import program_spans  # noqa: E402
from serve import Record  # noqa: E402
from test_bench_run import _BlankTrace, run_tiny  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("queue_wait_p50_ms.chat", "prefill_wall_p50_ms.chat")


def _read(name, run):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span"
    assert entry["workloads"] == ["chatglm3-tt.chat"]
    return harness.reader(entry, ROOT).read(run)


def _run(events, records=(), t0=100.0, seconds=51.0):
    return harness.Run(cell={}, cj={}, mix={}, tt={}, seconds=seconds,
                       setup_s=0.0, records=list(records), t0=t0,
                       t1=t0 + seconds, events=events)


_sid = iter(range(10_000))


def _span(name, t, dur, parent=-1, **fields):
    return {"ev": "span", "t": t, "name": name, "dur": dur,
            "sid": next(_sid), "parent": parent, **fields}


def _admit(t, rids):
    return [{"ev": "admit", "t": t, "rid": rid} for rid in rids]


def _events():
    """Traced interval [117, 127).  Requests: rid 0 before it, rids 1-2 in
    it, rid 3 admitted after it, rid 4 submitted before the window."""
    admit_12 = _span("serve/admit", 118.0195, 0.6, rids=[1, 2], n_tokens=900)
    return [
        {"ev": "submit", "t": 99.0, "rid": 4},
        {"ev": "submit", "t": 110.0, "rid": 0},
        _span("serve/admit", 110.4995, 0.7, rids=[0], n_tokens=512),
        *_admit(110.5, [0]),
        {"ev": "first_token", "t": 111.1, "rid": 0},
        {"ev": "submit", "t": 118.0, "rid": 1},
        {"ev": "submit", "t": 118.01, "rid": 2},
        admit_12,
        *_admit(118.02, [1, 2]),
        _span("serve/prefill_chunk", 118.03, 0.5, parent=admit_12["sid"],
              chunk=0, n_chunks=1),
        {"ev": "first_token", "t": 118.6, "rid": 1},
        {"ev": "first_token", "t": 118.6, "rid": 2},
        {"ev": "submit", "t": 126.9, "rid": 3},
        _span("serve/admit", 127.4995, 0.4, rids=[3, 4], n_tokens=64),
        *_admit(127.5, [3, 4]),
        {"ev": "first_token", "t": 127.9, "rid": 3},
        {"ev": "first_token", "t": 127.9, "rid": 4},
        _span("serve/host_bound", 116.9, 0.2),
        _span("serve/host_bound", 120.0, 0.2),
        _span("serve/host_bound", 126.95, 0.25),
        _span("serve/decode_dispatch", 118.7, 0.001, ahead=True, starved=True),
        _span("serve/decode_dispatch", 119.0, 0.001, ahead=True, starved=False),
        _span("serve/decode_dispatch", 120.3, 0.001, ahead=True, starved=False),
        _span("serve/decode_dispatch", 120.5, 0.001, ahead=True, starved=False),
        _span("serve/decode_dispatch", 122.0, 0.001, ahead=False, starved=False),
        _span("serve/decode_dispatch", 130.0, 0.001, ahead=True, starved=True),
        _span("serve/pump_idle", 121.0, 2.0),
        {"ev": "compile", "t": 119.5, "fun": "_decode", "stage": "compile",
         "dur": 0.3, "sid": -1},
    ]


def _records():
    return [Record(req=None, due=109.99, submitted=109.995, rid=0,
                   stamps=[111.2]),
            Record(req=None, due=117.995, submitted=117.999, rid=1,
                   stamps=[118.61]),
            Record(req=None, due=118.0, submitted=118.005, rid=2,
                   stamps=[118.62, 118.64])]


@pytest.mark.parametrize("seconds", [51.0, 6.0])
def test_traced_interval_is_what_the_tracer_profiles(seconds):
    tracer = harness._Tracer(seconds)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    lo, hi = program_spans.traced_interval(_run([], t0=100.0, seconds=seconds))
    assert lo == 100.0 + tracer.offset
    assert hi == pytest.approx(lo + tracer.length)


def test_queue_wait_and_prefill_read_the_requests_of_the_traced_prefix():
    run = _run(_events())
    # rids 0, 1, 2: waits 0.5, 0.02, 0.01 s; prefills 0.6, 0.58, 0.58 s
    assert _read("queue_wait_p50_ms.chat", run) == pytest.approx(20.0)
    assert _read("prefill_wall_p50_ms.chat", run) == pytest.approx(580.0)
    assert sorted(program_spans.requests(run)) == [0, 1, 2]


def test_host_bound_share_clips_to_the_traced_interval(capsys):
    run = _run(_events(), _records())
    # 0.1 + 0.2 + 0.05 s of the 10 s interval
    assert program_spans.host_bound_share(run) == pytest.approx(3.5)
    assert capsys.readouterr().err == ""
    # the queue-wait reader prints the traced third's line
    _read("queue_wait_p50_ms.chat", run)
    line = capsys.readouterr().err.strip()
    assert line.startswith("[spans] traced 10.000 s")
    assert "serve/pump_idle 2.0000" in line
    assert "serve/host_bound 0.3500 s (3.500 %)" in line
    assert "starved ahead dispatches 25.000 %" in line
    assert "compiles: _decode" in line
    # median-TTFT request of rids 0-2 (1.21, 0.615, 0.62 s): rid 2
    assert ("TTFT p50 over 3 requests: rid 2 620.000 ms = lateness 10.000 + queue 10.000 + "
            "prefill 580.000 + delivery 20.000 ms") in line


def test_ahead_starved_share_counts_ahead_dispatches_in_the_interval():
    assert program_spans.ahead_starved_share(_run(_events())) == pytest.approx(25.0)


def test_self_times_fill_the_interval():
    parts = program_spans.self_times(_run(_events()))
    assert parts["serve/admit"] == pytest.approx(0.1)
    assert parts["serve/prefill_chunk"] == pytest.approx(0.5)
    assert parts["serve/pump_idle"] == pytest.approx(2.0)
    assert "serve/host_bound" not in parts  # an overlay, reported on its own
    assert sum(parts.values()) == pytest.approx(10.0)


def test_a_program_without_spans_reads_nothing(capsys):
    """A program that records scheduler events but no spans reads no span
    quantity and prints no line; one that records nothing reads nothing."""
    events = [e for e in _events() if e["ev"] != "span"]
    run = _run(events, _records())
    assert program_spans.host_bound_share(run) is None
    assert program_spans.ahead_starved_share(run) is None
    assert program_spans.self_times(run)["no span"] == pytest.approx(10.0)
    program_spans.report(run)
    assert all(_read(name, _run(None)) is None for name in NEW)
    assert capsys.readouterr().err == ""


def test_queue_wait_and_prefill_read_a_program_without_spans(capsys):
    """The medians read the scheduler's events alone, so an observer that
    records no spans reads them the same."""
    run = _run([e for e in _events() if e["ev"] != "span"], _records())
    assert _read("queue_wait_p50_ms.chat", run) == pytest.approx(20.0)
    assert _read("prefill_wall_p50_ms.chat", run) == pytest.approx(580.0)
    assert capsys.readouterr().err == ""


def test_a_tiny_traced_run_reads_the_program_span_metrics(monkeypatch, capsys):
    monkeypatch.setattr(harness, "_Tracer", _BlankTrace)
    monkeypatch.setattr(harness, "peaks_for", lambda kind: peaks.PEAKS["TPU v5 lite"])
    res = run_tiny("tiny.open", seconds=3.0, trace=True)
    got = res["metrics"]
    assert set(NEW) <= set(got)
    assert got["queue_wait_p50_ms.chat"]["value"] >= 0
    assert got["prefill_wall_p50_ms.chat"]["value"] > 0
    line = next(ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("[spans]"))
    assert "serve/host_bound" in line and "starved ahead dispatches" in line
