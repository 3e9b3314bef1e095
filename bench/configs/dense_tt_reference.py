"""Plain reference of a decoder-only transformer whose linears may be
Tensor-Train matrices: straight ``jax.numpy`` in float32, every matrix
product at ``Precision.HIGHEST``, no kernels, no cache, no batching.

It follows the published architecture of the configurations that name it
(ChatGLM3-6B and Phi-4-mini-instruct): embedding lookup; per block an RMSNorm
(eps from the configuration), q/k/v projections (with biases where stated),
rotary positions on the first ``partial_rotary`` share of each head's
dimensions, causal grouped-query softmax attention, the output projection
added to the residual, a second RMSNorm and a SwiGLU MLP
``down(silu(gate(h)) * up(h))`` added to the residual; a final RMSNorm and
the unembedding (the embedding table when tied).  Departures from the
published models are listed in each configuration file.

A TT linear is the dense map its cores define.  Core ``k`` is held as a
matrix ``C_k`` of shape ``(r_{k-1} n_k, m_k r_k)``, rows ``(r_{k-1}, n_k)``
and columns ``(m_k, r_k)``, both row-major; the weight entry between input
index ``(j_1..j_d)`` and output index ``(i_1..i_d)`` (first mode most
significant) is ``sum over r of prod_k C_k[(r_{k-1}, j_k), (i_k, r_k)]``.
The reference builds that dense matrix and multiplies by it.

Nothing here imports the program.  The parameters are read from the tree
layout the program serves (``embed``, ``segments``, ``final_norm``,
``head``), which the benchmark fills with its own seeded weights.

``quant="fp8"`` computes every linear layer and the unembedding with both
operands rounded to float8 e4m3 (per output column for weights, per row for
activations, each scaled so its largest magnitude maps to 448): the control
that a correct program must not be confused with.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
QUERY_BLOCK = 512


def tt_dense_weight(cores, in_modes, out_modes, ranks):
    """(N, M) float32 matrix ``W^T`` of the TT linear ``y = x W^T``."""
    d = len(in_modes)
    t = cores[0].astype(jnp.float32).reshape(in_modes[0], out_modes[0], ranks[1])
    for k in range(1, d):
        c = cores[k].astype(jnp.float32).reshape(ranks[k], in_modes[k],
                                                 out_modes[k], ranks[k + 1])
        t = jnp.tensordot(t, c, axes=([t.ndim - 1], [0]), precision=HIGHEST)
    t = t.reshape([x for k in range(d) for x in (in_modes[k], out_modes[k])])
    perm = [2 * k for k in range(d)] + [2 * k + 1 for k in range(d)]
    return t.transpose(perm).reshape(math.prod(in_modes), math.prod(out_modes))


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x, w, quant):
    """x (S, N) @ w (N, M) in float32."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        gain.astype(jnp.float32)


def _rope(x, positions, partial, theta):
    """Rotate-half rotary positions on the first ``partial`` of each head."""
    rot = int(x.shape[-1] * partial)
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]], -1)


def _attention(q, k, v):
    """Causal softmax attention; q (S, H, Dh), k/v (S, Hkv, Dh)."""
    s_len, h, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    nb = s_len // QUERY_BLOCK
    qb = q.reshape(nb, QUERY_BLOCK, hkv, g, dh)
    keys = jnp.arange(s_len)

    def block(args):
        qi, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HIGHEST) / math.sqrt(dh)
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (qb, jnp.arange(nb)))
    return o.reshape(s_len, h * dh)


def _linear(x, p, tt, quant):
    if "cores" in p:
        w = tt_dense_weight(p["cores"], tt["in_modes"], tt["out_modes"],
                            tt["ranks"])
    else:
        w = p["w"].astype(jnp.float32)
    y = _matmul(x, w, quant)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def forward(params, model: dict, tt: dict, tokens, pick, *, quant=None):
    """Logits (K, V) float32 at positions ``pick`` (K,) of ``tokens`` (S,).

    ``model`` is a configuration's ``model`` object; ``tt`` maps each TT
    role to its ``in_modes``/``out_modes``/``ranks``.  ``S`` must be a
    multiple of 512; positions past the real sequence may hold any token
    (attention is causal, so they never reach a picked position).
    """
    eps = model["norm_eps"]
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    s_len = tokens.shape[0]
    positions = jnp.arange(s_len)
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def block(x, lp):
        a = lp["attn"]
        hn = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _linear(hn, a["wq"], None, quant).reshape(s_len, h, dh)
        k = _linear(hn, a["wk"], None, quant).reshape(s_len, hkv, dh)
        v = _linear(hn, a["wv"], None, quant).reshape(s_len, hkv, dh)
        q = _rope(q, positions, model["partial_rotary"], model["rope_theta"])
        k = _rope(k, positions, model["partial_rotary"], model["rope_theta"])
        x = x + _linear(_attention(q, k, v), a["wo"], tt.get("attn_o"), quant)
        m = lp["mlp"]
        hn = _rmsnorm(x, lp["ln2"]["scale"], eps)
        gate = jax.nn.silu(_linear(hn, m["gate"], tt.get("mlp_gate"), quant))
        up = _linear(hn, m["up"], tt.get("mlp_up"), quant)
        x = x + _linear(gate * up, m["down"], tt.get("mlp_down"), quant)
        return x, None

    for seg in params["segments"]:
        x, _ = jax.lax.scan(block, x, seg)
    x = _rmsnorm(x[pick], params["final_norm"]["scale"], eps)
    if model["tie_embeddings"]:
        w = params["embed"]["table"].astype(jnp.float32).T
    else:
        w = params["head"]["w"].astype(jnp.float32)
    return _matmul(x, w, quant)
