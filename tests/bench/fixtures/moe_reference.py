"""Plain reference of a decoder-only transformer whose every MLP is a
top-k mixture of experts: straight ``jax.numpy`` in float32, every matrix
product at ``Precision.HIGHEST``, no kernels, no cache, no batching.

Per block: RMSNorm, causal grouped-query softmax attention with rotary
positions (the helpers of ``bench/configs/dense_tt_reference.py``), the
output projection added to the residual; a second RMSNorm, the router's
softmax over all experts, the ``experts_per_token`` largest kept and
renormalised to sum to 1, and the kept experts' SwiGLU outputs
``down(silu(gate(h)) * up(h))`` summed with those weights and added to the
residual.  A final RMSNorm and the unembedding.  Every expert is computed
for every token and the unchosen ones weigh 0.

Nothing here imports the program.  The parameters are read from the tree
layout the program serves (``embed``, ``segments`` with ``moe/router`` and
``moe/experts`` stacked over experts, ``final_norm``, ``head``).

``leaf_rule`` draws the router's weights at the scale the configuration
states (``router_std``, a key only this reference reads) where the
benchmark's own rule would draw ``1 / sqrt(d_model)``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp


def _dense_reference():
    path = Path(__file__).resolve().parents[3] / "bench" / "configs" / "dense_tt_reference.py"
    spec = importlib.util.spec_from_file_location("reference_dense_tt_for_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_D = _dense_reference()


def leaf_rule(path: str, shape, cj: dict):
    if path.endswith("/moe/router/w"):
        return 0.0, cj["model"]["router_std"]
    return None


def _experts(h, e, quant):
    """(S, D) through every expert's SwiGLU: (E, S, D)."""
    def one(gate, up, down):
        g = jax.nn.silu(_D._matmul(h, gate.astype(jnp.float32), quant))
        u = _D._matmul(h, up.astype(jnp.float32), quant)
        return _D._matmul(g * u, down.astype(jnp.float32), quant)

    return jax.vmap(one)(e["gate"]["w"], e["up"]["w"], e["down"]["w"])


def forward(params, model: dict, tt: dict, tokens, pick, *, quant=None):
    """Logits (K, V) float32 at positions ``pick`` (K,) of ``tokens`` (S,);
    ``S`` a multiple of 512 (see ``dense_tt_reference.forward``)."""
    eps = model["norm_eps"]
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    k_top = model["experts_per_token"]
    s_len = tokens.shape[0]
    positions = jnp.arange(s_len)
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def block(x, lp):
        a = lp["attn"]
        hn = _D._rmsnorm(x, lp["ln1"]["scale"], eps)
        q = _D._linear(hn, a["wq"], None, quant).reshape(s_len, h, dh)
        k = _D._linear(hn, a["wk"], None, quant).reshape(s_len, hkv, dh)
        v = _D._linear(hn, a["wv"], None, quant).reshape(s_len, hkv, dh)
        q = _D._rope(q, positions, model["partial_rotary"], model["rope_theta"])
        k = _D._rope(k, positions, model["partial_rotary"], model["rope_theta"])
        x = x + _D._linear(_D._attention(q, k, v), a["wo"], None, quant)
        m = lp["moe"]
        hn = _D._rmsnorm(x, lp["ln2"]["scale"], eps)
        probs = jax.nn.softmax(_D._linear(hn, m["router"], None, quant), axis=-1)
        gates, chosen = jax.lax.top_k(probs, k_top)
        gates = gates / gates.sum(-1, keepdims=True)
        weight = jnp.zeros_like(probs).at[jnp.arange(s_len)[:, None], chosen].set(gates)
        y = jnp.einsum("se,esd->sd", weight, _experts(hn, m["experts"], quant),
                       precision=_D.HIGHEST)
        return x + y, None

    for seg in params["segments"]:
        x, _ = jax.lax.scan(block, x, seg)
    x = _D._rmsnorm(x[pick], params["final_norm"]["scale"], eps)
    return _D._matmul(x, params["head"]["w"].astype(jnp.float32), quant)
