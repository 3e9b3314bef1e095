"""Operations and bytes each kernel call needs, from the algorithm's shapes.

Counted from what a call computes (rows, TT modes and ranks, context
lengths), never from how a kernel tiles it, so a roofline share reads the
same work whatever implements it.  An operation is a multiply or an add
(a multiply-accumulate counts 2).  Bytes are the least the call has to move
between HBM and the chip: each input and output once.

This is the default ops module of a configuration: a dense transformer with
grouped-query attention, a SwiGLU MLP and TT linears on the roles of
``tt``.  A configuration of another shape names its own module (``ops`` in
its file) with the same two entries: ``sequence_flops(model, tt, contexts,
with_unembed)`` and ``KERNELS``, ``{kind: fn(kernel_event, run) -> (ops,
bytes) or None}``, where ``run`` has ``model``, ``tt`` and ``events`` (the
program's event log); ``None`` says the call cannot be counted, and the
kind then reads no roofline share.
"""
from __future__ import annotations

import math

BF16 = 2


def tt_row_flops(in_modes, out_modes, ranks) -> int:
    """Operations of one input row through the staged TT contraction
    (paper Eq. 4): stage k contracts ``r_{k-1} n_k`` into ``m_k r_k`` for
    every combination of the input modes still to come and the output
    modes already made."""
    total, m_done = 0, 1
    for k in range(len(in_modes)):
        t = math.prod(in_modes[k + 1:]) * m_done
        total += 2 * t * ranks[k] * in_modes[k] * out_modes[k] * ranks[k + 1]
        m_done *= out_modes[k]
    return total


def tt_core_params(in_modes, out_modes, ranks) -> int:
    return sum(ranks[k] * in_modes[k] * out_modes[k] * ranks[k + 1]
               for k in range(len(in_modes)))


def tt_linear_call(rows: int, tt: dict, *, residual: bool,
                   dtype_bytes: int = BF16) -> tuple[int, int]:
    """(ops, bytes) of one ``tt_linear`` call over ``rows`` rows: the
    contraction, and the input, output, residual and cores read once."""
    n, m, r = tt["in_modes"], tt["out_modes"], tt["ranks"]
    n_in, n_out = math.prod(n), math.prod(m)
    ops = rows * tt_row_flops(n, m, r)
    moved = rows * (n_in + n_out * (2 if residual else 1)) + tt_core_params(n, m, r)
    return ops, moved * dtype_bytes


def paged_attention_call(context_lens, n_heads: int, n_kv_heads: int,
                         head_dim: int, kv_bytes: int = BF16,
                         q_bytes: int = BF16) -> tuple[int, int]:
    """(ops, bytes) of one decode attention call: each active row's query
    against its ``L`` cached keys and values (scores and weighted sum, 4 H
    Dh L), reading those keys and values and the query, writing the out."""
    ops = moved = 0
    for n_ctx in context_lens:
        ops += 4 * n_heads * head_dim * n_ctx
        moved += 2 * n_ctx * n_kv_heads * head_dim * kv_bytes \
            + 2 * n_heads * head_dim * q_bytes
    return ops, moved


def prefill_attention_call(chunks, n_heads: int, n_kv_heads: int,
                           head_dim: int, kv_bytes: int = BF16,
                           q_bytes: int = BF16) -> tuple[int, int]:
    """(ops, bytes) of one chunked-prefill attention call.  ``chunks`` holds
    one ``(start, n)`` per active row: queries at positions
    ``start .. start+n-1`` attend causally to everything before them."""
    ops = moved = 0
    for start, n in chunks:
        keys = sum(start + i + 1 for i in range(n))
        ops += 4 * n_heads * head_dim * keys
        moved += 2 * (start + n) * n_kv_heads * head_dim * kv_bytes \
            + 2 * n * n_heads * head_dim * q_bytes
    return ops, moved


def token_flops(model: dict, tt: dict, block: int, context: int,
                unembed: bool) -> int:
    """Operations the served model needs for one token at position
    ``context - 1`` through block ``block`` (TT roles from ``tt`` where the
    block is compressed), or the unembedding when ``unembed``."""
    d, h, hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], \
        model["head_dim"]
    if unembed:
        return 2 * d * model["vocab_size"]
    q, kv, f = h * dh, hkv * dh, model["d_ff"]
    ttd = model.get("ttd")
    is_tt = bool(ttd) and block >= ttd["first_tt_block"]

    def lin(role, n_in, n_out):
        if is_tt and role in tt:
            t = tt[role]
            return tt_row_flops(t["in_modes"], t["out_modes"], t["ranks"])
        return 2 * n_in * n_out

    return (2 * d * (q + 2 * kv) + 4 * h * dh * context + lin("attn_o", q, d)
            + lin("mlp_gate", d, f) + lin("mlp_up", d, f) + lin("mlp_down", f, d))


def sequence_flops(model: dict, tt: dict, contexts, with_unembed: int) -> int:
    """Operations for tokens at the given context lengths through every
    block, plus ``with_unembed`` unembeddings.  A token's operations are
    its context-free part plus ``4 H Dh`` per context position per block."""
    contexts = list(contexts)
    fixed = sum(token_flops(model, tt, b, 0, False)
                for b in range(model["n_layers"]))
    per_ctx = 4 * model["n_heads"] * model["head_dim"] * model["n_layers"]
    return (len(contexts) * fixed + per_ctx * sum(contexts)
            + with_unembed * token_flops(model, tt, 0, 0, True))


# -- the kernels of the trace, counted per call --------------------------------
def tt_role(tt: dict, n_in: int, n_out: int):
    """The TT role of ``n_in`` inputs and ``n_out`` outputs, or ``None``."""
    for role, t in tt.items():
        if math.prod(t["in_modes"]) == n_in and math.prod(t["out_modes"]) == n_out:
            return role
    return None


def _tt_linear(k: dict, run):
    rows, n_out = k["result"]
    role = tt_role(run.tt, k["operands"][0][1][-1], n_out)
    if role is None:
        return None
    return tt_linear_call(rows, run.tt[role],
                          residual=role in ("attn_o", "mlp_down"))


def _paged_attention(k: dict, run):
    if k["call"] is None:
        return None
    m = run.model
    return paged_attention_call([int(p) + 1 for p in k["call"]["positions"] if p >= 0],
                                m["n_heads"], m["n_kv_heads"], m["head_dim"])


def _prefill_attention(k: dict, run):
    if k["call"] is None:
        return None
    m = run.model
    return prefill_attention_call(k["call"]["chunks"], m["n_heads"],
                                  m["n_kv_heads"], m["head_dim"])


KERNELS = {"tt_linear": _tt_linear, "paged_attention": _paged_attention,
           "prefill_attention": _prefill_attention}
