"""The trace reduction on a small recorded trace."""
import sys
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(ROOT / "bench"))

import devtrace  # noqa: E402
import opcount  # noqa: E402

MS = 1e-3
T0 = 1000.1  # the fixture's first device event, on the trace clock
OFFSET = 995.0  # trace clock minus the host's perf_counter

TT = {"attn_o": {"in_modes": (4, 4, 4), "out_modes": (4, 4, 4), "ranks": (1, 4, 4, 1)}}
MODEL = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_model": 64,
         "d_ff": 96, "vocab_size": 512, "n_layers": 1, "ttd": {"first_tt_block": 0}}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
DECODE_POS = [np.array([20, 7, -1, -1]), np.array([21, 8, -1, -1])]


@pytest.fixture(scope="module")
def ev():
    return devtrace.events(ProfileData.from_text_proto(
        (FIX / "trace_small.textproto").read_text()))


@pytest.fixture(scope="module")
def red(ev):
    calls = {
        "prefill": {"times": [T0 - 1 * MS],
                    "info": devtrace.prefill_calls([(0.0, [20, 7])], 32)},
        "decode": {"times": [T0 + 11 * MS, T0 + 19 * MS],
                   "info": [{"positions": p} for p in DECODE_POS]},
    }
    return devtrace.reduce_events(ev, (T0, T0 + 30 * MS), calls)


def test_events_split_by_line(ev):
    assert [m["name"] for m in ev["modules"]][:3] == \
        ["jit__prefill(11)", "jit__decode(12)", "jit__decode(12)"]
    assert len(ev["ops"]) == 10
    assert ev["ops"][0]["start"] == pytest.approx(T0)
    assert {s["name"] for s in ev["spans"]} >= {"bench/admit", "bench/decode_collect"}


def test_busy_union_and_idle_share(red):
    # ops cover 0-10, 12-16, 20-24 and 26-27 ms of a 30 ms window
    assert red["window_s"] == pytest.approx(30 * MS)
    assert red["busy_s"] == pytest.approx(19 * MS)
    assert devtrace.idle_share(red) == pytest.approx(100 * 11 / 30)
    assert devtrace.union_seconds([(0, 2), (1, 3), (5, 6)], 0.5, 10) == pytest.approx(3.5)


def test_kernel_time_by_name(red):
    by = {}
    for k in red["kernel_events"]:
        by[(k["prog"], k["kind"])] = by.get((k["prog"], k["kind"]), 0) + k["dur"]
    assert by == pytest.approx({("prefill", "tt_linear"): 3 * MS,
                                ("prefill", "prefill_attention"): 3 * MS,
                                ("decode", "tt_linear"): 4 * MS,
                                ("decode", "paged_attention"): 2 * MS})
    assert devtrace.program_ms(red, "decode") == pytest.approx(4.0)


def test_executions_pair_with_their_calls(red):
    dec = red["execs"]["decode"]
    assert [list(m["call"]["positions"]) for m in dec] == [list(p) for p in DECODE_POS]
    assert red["execs"]["prefill"][0]["call"] == {"chunks": [(0, 20), (0, 7)],
                                                  "finishing": 2}
    # an execution can only follow its dispatch
    assert devtrace.align([1.0, 2.0], [0.5, 1.5, 2.5]) == 0
    assert devtrace.align([1.0, 2.0], [0.2, 0.5, 1.5]) == 1
    assert devtrace.align([1.0], []) is None


def test_clock_offset_from_numbered_spans(ev):
    host = [T0 + 11 * MS - OFFSET, T0 + 19 * MS - OFFSET]
    assert devtrace.clock_offset(ev["spans"], "bench/decode_dispatch", host) == \
        pytest.approx(OFFSET)
    assert devtrace.clock_offset(ev["spans"], "bench/nothing", host) is None


def test_kernels_are_told_apart_by_their_instruction(ev):
    kinds = [k["kind"] for k in map(devtrace.parse_kernel, (o["name"] for o in ev["ops"])) if k]
    assert kinds == ["tt_linear", "prefill_attention", "tt_linear", "paged_attention",
                     "tt_linear", "paged_attention"]
    tt = devtrace.parse_kernel(ev["ops"][1]["name"])
    assert tt["kind"] == "tt_linear" and tt["result"] == (64, 64)
    assert tt["operands"] == [("bf16", (64, 64)), ("bf16", (16, 16)), ("bf16", (64, 64))]
    assert devtrace.parse_kernel(ev["ops"][2]["name"])["kind"] == "prefill_attention"
    assert devtrace.parse_kernel(ev["ops"][5]["name"])["result"] == (4, 4, 16)
    assert devtrace.parse_kernel(ev["ops"][0]["name"]) is None
    assert devtrace.short_name(ev["ops"][0]["name"]) == "fusion"


def test_roofline_is_least_time_over_device_time(red):
    need = 0.0
    for rows, n in ((64, 1), (4, 2)):
        ops, moved = opcount.tt_linear_call(rows, TT["attn_o"], residual=True)
        need += n * max(ops / PEAKS["bf16_flops"], moved / PEAKS["hbm_bytes_per_s"])
    share, mem = devtrace.roofline_of(red["kernel_events"], "tt_linear", TT, MODEL, PEAKS)
    assert share == pytest.approx(100 * need / (7 * MS))
    assert mem == 1.0
    need = 0.0
    for pos in DECODE_POS:
        ops, moved = opcount.paged_attention_call([int(p) + 1 for p in pos if p >= 0],
                                                  4, 2, 16)
        need += max(ops / PEAKS["bf16_flops"], moved / PEAKS["hbm_bytes_per_s"])
    share, _ = devtrace.roofline_of(red["kernel_events"], "paged_attention", TT,
                                    MODEL, PEAKS)
    assert share == pytest.approx(100 * need / (2 * MS))
    assert devtrace.roofline_of(red["kernel_events"], "int4_matmul", TT, MODEL,
                                PEAKS) is None


def test_useful_flops_leave_out_padding(red):
    flops = devtrace.useful_flops(red["execs"], MODEL, TT)
    prompt = [i + 1 for i in range(20)] + [i + 1 for i in range(7)]
    want = opcount.sequence_flops(MODEL, TT, prompt, 2)
    for pos in DECODE_POS:
        ctx = [int(p) + 1 for p in pos if p >= 0]
        want += opcount.sequence_flops(MODEL, TT, ctx, len(ctx))
    assert flops == want


def test_breakdown_names_ops_and_gaps(red, ev):
    b = devtrace.breakdown(red, ev["spans"], (T0, T0 + 30 * MS))
    top = dict(b["device_ops"])
    assert top["prefill:fusion"] == pytest.approx(4 * MS)
    assert top["decode:tt_linear"] == pytest.approx(4 * MS)
    assert top["other:fusion"] == pytest.approx(1 * MS)
    gaps = b["idle_gaps"]
    # the longest gap, 16-20 ms, is spent after the collect span ended
    assert gaps[0] == ["no benchmark span", pytest.approx(4 * MS)]
    # 10-12 ms: the host was admitting, then launching tick 0
    assert ["bench/decode_dispatch", pytest.approx(2 * MS)] in gaps or \
        ["bench/admit", pytest.approx(2 * MS)] in gaps
