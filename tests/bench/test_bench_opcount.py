"""Operation and byte counts of the kernels against hand counts."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import opcount  # noqa: E402

# chatglm3's attn_o: in (16, 8, 8, 4), out (4, 8, 8, 16), ranks (1, 16, 16, 16, 1)
ATTN_O = {"in_modes": (16, 8, 8, 4), "out_modes": (4, 8, 8, 16),
          "ranks": (1, 16, 16, 16, 1)}


def test_tt_row_flops_by_hand():
    # stage k: 2 * (prod of inputs still to come * outputs made) * r n * m r'
    s1 = 2 * (8 * 8 * 4) * (1 * 16) * (4 * 16)
    s2 = 2 * (8 * 4 * 4) * (16 * 8) * (8 * 16)
    s3 = 2 * (4 * 4 * 8) * (16 * 8) * (8 * 16)
    s4 = 2 * (1 * 4 * 8 * 8) * (16 * 4) * (16 * 1)
    assert opcount.tt_row_flops(ATTN_O["in_modes"], ATTN_O["out_modes"],
                                ATTN_O["ranks"]) == s1 + s2 + s3 + s4


def test_tt_linear_call_by_hand():
    cores = 16 * 64 + 128 * 128 + 128 * 128 + 64 * 16
    rows = 32
    ops, moved = opcount.tt_linear_call(rows, ATTN_O, residual=True)
    assert ops == rows * opcount.tt_row_flops(ATTN_O["in_modes"],
                                              ATTN_O["out_modes"], ATTN_O["ranks"])
    # bf16: input, output and residual rows, and the cores once
    assert moved == 2 * (rows * (4096 + 2 * 4096) + cores)
    _, moved_plain = opcount.tt_linear_call(rows, ATTN_O, residual=False)
    assert moved - moved_plain == 2 * rows * 4096


def test_paged_attention_call_by_hand():
    # two active rows with 10 and 3 cached tokens; 32 heads, 2 kv heads, 128 wide
    ops, moved = opcount.paged_attention_call([10, 3], 32, 2, 128)
    assert ops == 4 * 32 * 128 * 13
    assert moved == 2 * 13 * 2 * 128 * 2 + 2 * (2 * 32 * 128 * 2)
    assert opcount.paged_attention_call([], 32, 2, 128) == (0, 0)


def test_prefill_attention_call_by_hand():
    # one row: queries at positions 4..6 see 5, 6 and 7 keys
    ops, moved = opcount.prefill_attention_call([(4, 3)], 4, 2, 16)
    assert ops == 4 * 4 * 16 * (5 + 6 + 7)
    assert moved == 2 * 7 * 2 * 16 * 2 + 2 * 3 * 4 * 16 * 2


def test_sequence_flops_adds_up_token_flops():
    model = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
             "d_ff": 96, "vocab_size": 512, "n_layers": 3,
             "ttd": {"first_tt_block": 1}}
    tt = {"attn_o": {"in_modes": (4, 4, 4), "out_modes": (4, 4, 4),
                     "ranks": (1, 4, 4, 1)}}
    contexts = [1, 5, 9]
    by_token = sum(opcount.token_flops(model, tt, b, c, False)
                   for c in contexts for b in range(3))
    by_token += 2 * opcount.token_flops(model, tt, 0, 0, True)
    assert opcount.sequence_flops(model, tt, contexts, 2) == by_token
    # a TT block differs from a dense one by the TT role's contraction alone
    dense = 2 * 64 * 64
    ttf = opcount.tt_row_flops((4, 4, 4), (4, 4, 4), (1, 4, 4, 1))
    assert opcount.token_flops(model, tt, 0, 1, False) - \
        opcount.token_flops(model, tt, 1, 1, False) == dense - ttf
