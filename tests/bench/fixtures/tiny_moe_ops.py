"""Operations of the tiny mixture-of-experts fixture.

Per token and block: the q/k/v/o projections, ``4 H Dh`` per context
position of attention, the router, and ``experts_per_token`` routed SwiGLU
experts of width ``d_ff_expert`` (not a dense ``d_ff``, and not the experts
a token is not routed to); one unembedding per finished prompt or decoded
token.  Its kernels are the paged and prefill attention, counted as the
default module counts them.
"""
import opcount


def sequence_flops(model: dict, tt: dict, contexts, with_unembed: int) -> int:
    d, h, hkv, dh = model["d_model"], model["n_heads"], model["n_kv_heads"], \
        model["head_dim"]
    q, kv = h * dh, hkv * dh
    experts = model["experts_per_token"] * 3 * 2 * d * model["d_ff_expert"]
    fixed = 2 * d * (q + 2 * kv) + 2 * q * d + 2 * d * model["n_experts"] + experts
    contexts = list(contexts)
    return (model["n_layers"] * (len(contexts) * fixed + 4 * h * dh * sum(contexts))
            + with_unembed * 2 * d * model["vocab_size"])


KERNELS = {kind: opcount.KERNELS[kind]
           for kind in ("paged_attention", "prefill_attention")}
