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


def _mosaic(name: str, result: str, operands: list[str]) -> str:
    """A Mosaic kernel's HLO instruction as the trace names it."""
    args = ", ".join(f"{o} %x{i}" for i, o in enumerate(operands))
    return (f'%{name} = {result}{{1,0}} custom-call({args}), '
            f'custom_call_target="tpu_custom_call", '
            f'operand_layout_constraints={{{", ".join(operands)}}}')


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
    # a kernel takes its kind from its instruction's name, whatever its shape
    named = {name: devtrace.parse_kernel(_mosaic(name, result, operands))
             for name, result, operands in (
                 ("tt_linear.48", "bf16[8,64]", ["bf16[8,64]", "bf16[16,16]"]),
                 ("paged_attention.3", "bf16[4,4,16]", ["s32[8]", "s32[4]", "bf16[4,4,16]"]),
                 ("prefill_attention.12.1", "bf16[2,32,4,16]", ["s32[8]", "s32[2]", "bf16[2,32,4,16]"]),
                 ("mla_decode.2", "bf16[8,64]", ["bf16[8,64]", "bf16[16,16]"]))}
    assert {n: k["kind"] for n, k in named.items()} == {
        "tt_linear.48": "tt_linear",
        "paged_attention.3": "paged_attention",
        "prefill_attention.12.1": "prefill_attention",
        "mla_decode.2": "mla_decode"}  # its own kind, though shaped like a tt_linear
    assert named["mla_decode.2"]["result"] == (8, 64)


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


MOE = {"family": "moe", "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 16, "d_ff": 32, "n_experts": 8, "experts_per_token": 2,
       "d_ff_expert": 32, "vocab_size": 512}


def test_a_configurations_own_ops_module_counts_its_model(red, monkeypatch):
    import model as bench_model
    cj = bench_model.load_config(FIX / "tiny-moe.json")
    ops = bench_model.ops_module(FIX / "tiny-moe.json", cj)
    assert Path(ops.__file__).name == "tiny_moe_ops.py"
    assert bench_model.ops_module(FIX / "tiny-tt.json",
                                  bench_model.load_config(FIX / "tiny-tt.json")) is opcount

    def dense_count(*a, **k):
        raise AssertionError("the default module's dense count was used")

    monkeypatch.setattr(opcount, "token_flops", dense_count)
    flops = devtrace.useful_flops(red["execs"], MOE, {}, ops)
    prompt = [i + 1 for i in range(20)] + [i + 1 for i in range(7)]
    want = ops.sequence_flops(MOE, {}, prompt, 2)
    for pos in DECODE_POS:
        ctx = [int(p) + 1 for p in pos if p >= 0]
        want += ops.sequence_flops(MOE, {}, ctx, len(ctx))
    assert flops == want
    # 2 routed experts of width 32 a token, not a dense d_ff
    per_token = ops.sequence_flops(MOE, {}, [0], 0)
    assert per_token == 2 * (2 * 64 * (64 + 2 * 32) + 2 * 64 * 64 + 2 * 64 * 8
                             + 2 * 3 * 2 * 64 * 32)
    # its kernels: the attention it runs, and no tt_linear
    monkeypatch.setattr(opcount, "KERNELS", {})
    share, _ = devtrace.roofline_of(red["kernel_events"], "paged_attention", {}, MOE,
                                    PEAKS, ops=ops)
    assert share > 0
    assert devtrace.roofline_of(red["kernel_events"], "tt_linear", TT, MOE, PEAKS,
                                ops=ops) is None


def test_a_kernel_kind_counts_through_the_ops_table(red):
    seen = []

    class Ops:
        KERNELS = {"tt_linear": lambda k, run: seen.append((k["result"], run)) or (1, 1)}

    devtrace.roofline_of(red["kernel_events"], "tt_linear", TT, MODEL, PEAKS,
                         ops=Ops, events=["e"])
    assert [r for r, _ in seen] == [(64, 64), (4, 64), (4, 64)]
    run = seen[0][1]
    assert (run.model, run.tt, run.events) == (MODEL, TT, ["e"])


def test_a_call_the_ops_table_cannot_count_leaves_the_share_unread(red):
    # a share of the other calls would be biased: none is read, as an
    # attention call without its pairing reads none
    class Ops:
        KERNELS = {"tt_linear": lambda k, run: None if k["result"][0] == 4 else (1, 1)}

    assert devtrace.roofline_of(red["kernel_events"], "tt_linear", TT, MODEL, PEAKS,
                                ops=Ops) is None
    unpaired = [dict(k, call=None) if k["kind"] == "paged_attention" else k
                for k in red["kernel_events"]]
    assert devtrace.roofline_of(unpaired, "paged_attention", TT, MODEL, PEAKS) is None
    assert devtrace.roofline_of(unpaired, "tt_linear", TT, MODEL, PEAKS) == \
        devtrace.roofline_of(red["kernel_events"], "tt_linear", TT, MODEL, PEAKS)
    # a tt_linear of no TT role cannot be counted either
    assert devtrace.roofline_of(red["kernel_events"], "tt_linear",
                                {"mlp_up": TT["attn_o"] | {"in_modes": (2, 4, 4)}},
                                MODEL, PEAKS) is None


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
