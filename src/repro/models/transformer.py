"""Decoder-only transformer family: dense, MoE, and M-RoPE (VLM backbone).

Covers kimi-k2, mixtral, phi4-mini, tinyllama, qwen1.5-110b, granite-3,
qwen2-vl, chatglm3-6b, llama2-7b.  Layers are stacked and scanned; the layer
stack is split into *segments* so the paper's "compress only k of L blocks"
recipe keeps scan homogeneity (each segment is internally homogeneous).

Sequence-parallel convention: between blocks activations are sharded
(batch → data/pod, seq → model); inside attention/MLP the seq dim is gathered
and heads / d_ff take over the model axis (Megatron-SP, driven purely by
sharding constraints — XLA inserts the all-gather / reduce-scatter pairs).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..dist import constrain
from ..dist.api import BATCH
from ..kernels import dispatch
from .modules import (
    LinearSpec,
    apply_linear,
    apply_mlp,
    apply_norm,
    apply_rope,
    attention_dense,
    dt,
    embed_lookup,
    embed_spec,
    flash_attention,
    init_embed,
    init_linear,
    init_mlp,
    init_norm,
    linear_spec,
    mlp_specs,
    paged_kv_update,
    remat_wrap,
    ring_kv_update,
    rope_angles,
    stack_init,
    unembed,
)
from .moe import apply_moe, init_moe, moe_specs


# ---------------------------------------------------------------------------
# Static block specs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class BlockSpecs:
    attn: tuple[tuple[str, LinearSpec], ...]
    mlp: tuple[tuple[str, LinearSpec], ...] | None
    moe: Any | None  # dict from moe_specs (hashable enough for our use)

    def attn_d(self):
        return dict(self.attn)

    def mlp_d(self):
        return dict(self.mlp) if self.mlp is not None else None


def make_block_specs(cfg: ModelConfig, ttd_block: bool) -> BlockSpecs:
    attn = (
        ("wq", linear_spec(cfg, "attn_q", cfg.d_model, cfg.q_dim, bias=cfg.qkv_bias, ttd_block=ttd_block)),
        ("wk", linear_spec(cfg, "attn_k", cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias, ttd_block=ttd_block)),
        ("wv", linear_spec(cfg, "attn_v", cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias, ttd_block=ttd_block)),
        ("wo", linear_spec(cfg, "attn_o", cfg.q_dim, cfg.d_model, ttd_block=ttd_block)),
    )
    if cfg.family == "moe":
        return BlockSpecs(attn, None, moe_specs(cfg, ttd_block))
    return BlockSpecs(attn, tuple(mlp_specs(cfg, ttd_block).items()), None)


def segment_plan(cfg: ModelConfig) -> list[tuple[int, bool]]:
    """[(n_layers, ttd_enabled_for_these_blocks), ...]"""
    ft = cfg.ttd.first_tt_block if cfg.ttd.enabled else cfg.n_layers
    ft = max(0, min(ft, cfg.n_layers))
    segs = []
    if ft > 0:
        segs.append((ft, False))
    if cfg.n_layers - ft > 0:
        segs.append((cfg.n_layers - ft, True))
    return segs


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_block(key, cfg: ModelConfig, specs: BlockSpecs, param_dtype):
    keys = jax.random.split(key, 6)
    p = {
        "ln1": init_norm(cfg, cfg.d_model, param_dtype),
        "ln2": init_norm(cfg, cfg.d_model, param_dtype),
        "attn": {nm: init_linear(k, sp, param_dtype)
                 for (nm, sp), k in zip(specs.attn, jax.random.split(keys[0], 4))},
    }
    if specs.moe is not None:
        p["moe"] = init_moe(keys[1], cfg, specs.moe, param_dtype)
    else:
        p["mlp"] = init_mlp(keys[1], specs.mlp_d(), param_dtype)
    return p


def init_lm(key, cfg: ModelConfig):
    param_dtype = dt(cfg.param_dtype)
    keys = jax.random.split(key, 4 + len(segment_plan(cfg)))
    params: dict[str, Any] = {"embed": init_embed(keys[0], cfg, param_dtype)}
    segments = []
    for i, (n, ttd_on) in enumerate(segment_plan(cfg)):
        specs = make_block_specs(cfg, ttd_on)
        segments.append(stack_init(lambda k, s=specs: init_block(k, cfg, s, param_dtype), keys[2 + i], n))
    params["segments"] = segments
    params["final_norm"] = init_norm(cfg, cfg.d_model, param_dtype)
    if not cfg.tie_embeddings:
        std = 1.0 / math.sqrt(cfg.d_model)
        params["head"] = {"w": (jax.random.normal(keys[1], (cfg.d_model, cfg.vocab_size), jnp.float32) * std).astype(param_dtype)}
    return params


# ---------------------------------------------------------------------------
# Attention (shared by train / prefill / decode)
# ---------------------------------------------------------------------------
def _qkv(params, specs: BlockSpecs, cfg: ModelConfig, x, rope_cs, compute_dtype):
    a = specs.attn_d()
    b, s, _ = x.shape
    q = apply_linear(params["attn"]["wq"], x, a["wq"], compute_dtype).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = apply_linear(params["attn"]["wk"], x, a["wk"], compute_dtype).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = apply_linear(params["attn"]["wv"], x, a["wv"], compute_dtype).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if rope_cs is not None:
        cos, sin = rope_cs
        q = apply_rope(q, cos, sin, cfg.partial_rotary)
        k = apply_rope(k, cos, sin, cfg.partial_rotary)
    q = constrain(q, BATCH, None, "model", None)
    k = constrain(k, BATCH, None, "model", None)
    v = constrain(v, BATCH, None, "model", None)
    return q, k, v


def attn_full(params, specs, cfg: ModelConfig, x, rope_cs, compute_dtype,
              *, return_kv=False, residual=None):
    """Self-attention over the whole sequence (train / prefill).

    ``residual`` (the block's skip connection) fuses into the output
    projection's epilogue — the paper's TTDLinear-Res at the attn-out site.
    """
    b, s, _ = x.shape
    q, k, v = _qkv(params, specs, cfg, x, rope_cs, compute_dtype)
    pos = jnp.arange(s, dtype=jnp.int32)
    o = flash_attention(q, k, v, qpos=pos, kpos=pos, causal=True, window=cfg.window,
                        q_block=cfg.q_block, kv_block=cfg.kv_block)
    o = constrain(o, BATCH, None, "model", None)
    o = o.reshape(b, s, cfg.q_dim)
    if specs.attn_d()["wo"].kind == "tt":
        # SP boundary: heads→seq reshard so the TT segment stays token-sharded
        o = constrain(o, BATCH, "model", None)
    o = apply_linear(params["attn"]["wo"], o, specs.attn_d()["wo"], compute_dtype,
                     residual=residual)
    return (o, (k, v)) if return_kv else (o, None)


def attn_decode(params, specs, cfg: ModelConfig, x, rope_cs, cache, pos,
                compute_dtype, residual=None):
    """One-token decode against a (ring) KV cache.

    cache: {"k": (B, W, Hkv, Dh), "v": ..., "pos": (W,) int32, -1 = empty}.
    ``pos`` is the absolute position of the new token (scalar int32).
    """
    b, s, _ = x.shape  # s == 1
    q, k, v = _qkv(params, specs, cfg, x, rope_cs, compute_dtype)
    w = cache["k"].shape[1]
    slot = (pos % w).astype(jnp.int32)
    k_new = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    v_new = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    pos_new = jax.lax.dynamic_update_slice(cache["pos"], pos[None].astype(jnp.int32), (slot,))
    kmask = pos_new >= 0
    qpos = pos[None].astype(jnp.int32)
    o = attention_dense(q, k_new, v_new, qpos=qpos, kpos=pos_new, kmask=kmask,
                        causal=True, window=cfg.window)
    o = constrain(o, BATCH, None, "model", None)
    o = apply_linear(params["attn"]["wo"], o.reshape(b, s, cfg.q_dim),
                     specs.attn_d()["wo"], compute_dtype, residual=residual)
    return o, {"k": k_new, "v": v_new, "pos": pos_new}


def attn_paged(params, specs, cfg: ModelConfig, x, rope_cs, cache, block_tables,
               positions, compute_dtype, residual=None):
    """Attention against a paged KV cache (serve path; DESIGN.md §6).

    cache: one layer's ``{"k","v"[, "k_scale","v_scale"]}`` block pool;
    positions: (B, S) absolute token positions (``-1`` = padding, routed to
    the null block and masked out).  S == 1 is the decode shape and runs the
    fused Pallas kernel via ``kernels.dispatch.paged_attention``; S > 1 is a
    chunked-prefill step and runs the ragged prefill flash-attention kernel
    via ``kernels.dispatch.prefill_attention`` (both with the gather oracle
    as their ``ref`` backend).
    """
    b, s, _ = x.shape
    q, k, v = _qkv(params, specs, cfg, x, rope_cs, compute_dtype)
    new_cache = paged_kv_update(cache, k, v, block_tables, positions)
    if s == 1:
        o = dispatch.paged_attention(q[:, 0], new_cache, block_tables,
                                     positions[:, 0])[:, None]
    else:
        o = dispatch.prefill_attention(q, positions, cache=new_cache,
                                       block_tables=block_tables)
    o = constrain(o.astype(compute_dtype), BATCH, None, "model", None)
    o = apply_linear(params["attn"]["wo"], o.reshape(b, s, cfg.q_dim),
                     specs.attn_d()["wo"], compute_dtype, residual=residual)
    return o, new_cache


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------
def apply_block(params, specs: BlockSpecs, cfg: ModelConfig, x, rope_cs,
                compute_dtype, cache=None, pos=None):
    h = apply_norm(params["ln1"], x, cfg)
    if cache is None:
        a, _ = attn_full(params, specs, cfg, h, rope_cs, compute_dtype, residual=x)
        new_cache = None
    else:
        a, new_cache = attn_decode(params, specs, cfg, h, rope_cs, cache, pos,
                                   compute_dtype, residual=x)
    x = constrain(a.astype(x.dtype), BATCH, "model", None)
    h = apply_norm(params["ln2"], x, cfg)
    if specs.moe is not None:
        # MoE combine is gated per token-expert pair — the skip connection
        # can't ride a single linear's epilogue; added after the combine.
        m, aux = apply_moe(params["moe"], h, specs.moe, cfg, compute_dtype)
        x = x + m.astype(x.dtype)
    else:
        x = apply_mlp(params["mlp"], h, specs.mlp_d(), cfg, compute_dtype,
                      residual=x).astype(x.dtype)
        aux = jnp.zeros((), jnp.float32)
    x = constrain(x, BATCH, "model", None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Full forward (train / prefill) and decode step
# ---------------------------------------------------------------------------
def _rope_tables(cfg: ModelConfig, positions, b, s):
    if cfg.pos_type == "rope":
        if positions is None:
            positions = jnp.arange(s, dtype=jnp.int32)
        return rope_angles(positions, cfg.head_dim, cfg.rope_theta, cfg.partial_rotary)
    if cfg.pos_type == "mrope":
        if positions is None:
            p = jnp.arange(s, dtype=jnp.int32)
            positions = jnp.broadcast_to(p, (3, b, s))
        return rope_angles(positions, cfg.head_dim, cfg.rope_theta, cfg.partial_rotary,
                           mrope_sections=cfg.mrope_sections)
    return None


def forward(params, cfg: ModelConfig, tokens, positions=None, *, remat="none",
            inputs_embeds=None):
    """tokens: (B, S) int32 -> logits (B, S, V) f32, aux scalar."""
    compute_dtype = dt(cfg.compute_dtype)
    b, s = tokens.shape[:2]
    x = inputs_embeds if inputs_embeds is not None else embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, "model", None)
    rope_cs = _rope_tables(cfg, positions, b, s)
    aux_total = jnp.zeros((), jnp.float32)
    for seg_params, (n, ttd_on) in zip(params["segments"], segment_plan(cfg)):
        specs = make_block_specs(cfg, ttd_on)

        def body(carry, layer_params, specs=specs):
            y, _, aux = apply_block(layer_params, specs, cfg, carry, rope_cs, compute_dtype)
            return y, aux

        f = remat_wrap(body, remat)
        x, auxs = jax.lax.scan(lambda c, p: f(c, p), x, seg_params)
        aux_total = aux_total + auxs.sum()
    x = apply_norm(params["final_norm"], x, cfg)
    return x, aux_total


def logits_from_hidden(params, cfg: ModelConfig, x, compute_dtype=None):
    compute_dtype = compute_dtype or dt(cfg.compute_dtype)
    if cfg.tie_embeddings and "cores" in params["embed"]:
        # tied TT embedding: the unembed IS the TT linear — the cores'
        # (M, N) = (V, D) weight maps (…, D) -> (…, V) directly
        sp = embed_spec(cfg)
        if sp is None:
            raise ValueError(
                "embed params carry TT cores but cfg.ttd.embed is off")
        backend = dispatch.resolve_backend(None, role="unembed",
                                           preferred=sp.backend)
        return dispatch.tt_linear(x.astype(jnp.float32), params["embed"]["cores"],
                                  sp.tt, backend=backend, role="unembed")
    table = params["embed"]["table"] if cfg.tie_embeddings else params["head"]["w"].T
    return unembed(x, table, compute_dtype)


def head_weight(params, cfg: ModelConfig):
    """(D, V) unembedding weight (tied or separate)."""
    if cfg.tie_embeddings:
        if "cores" in params["embed"]:
            raise ValueError(
                "tied TT-compressed embedding has no dense head weight — "
                "logits go through logits_from_hidden's TT unembed path; "
                "reconstruct via core.ttd.tt_reconstruct if a dense (D, V) "
                "matrix is genuinely needed")
        return params["embed"]["table"].T
    return params["head"]["w"]


def init_cache(cfg: ModelConfig, batch: int, max_len: int, cache_dtype=jnp.bfloat16):
    """Stacked per-layer ring caches.  Ring size = window if SWA else max_len."""
    w = min(cfg.window, max_len) if cfg.window else max_len
    def one(n):
        return {
            "k": jnp.zeros((n, batch, w, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
            "v": jnp.zeros((n, batch, w, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
            "pos": jnp.full((n, w), -1, jnp.int32),
        }
    return [one(n) for n, _ in segment_plan(cfg)]


def decode_step(params, cfg: ModelConfig, caches, tokens, pos, positions=None):
    """tokens: (B, 1); pos: scalar int32 absolute position.
    Returns logits (B, V) f32 and updated caches."""
    compute_dtype = dt(cfg.compute_dtype)
    b = tokens.shape[0]
    x = embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, None, None)
    if positions is None:
        rope_pos = jnp.broadcast_to(pos[None], (1,)).astype(jnp.int32)
    else:
        rope_pos = positions
    rope_cs = _rope_tables(cfg, rope_pos if cfg.pos_type != "mrope" else positions, b, 1)
    new_caches = []
    for seg_params, seg_cache, (n, ttd_on) in zip(params["segments"], caches, segment_plan(cfg)):
        specs = make_block_specs(cfg, ttd_on)

        def body(carry, xs, specs=specs):
            layer_params, layer_cache = xs
            y, new_cache, _ = apply_block(layer_params, specs, cfg, carry, rope_cs,
                                          compute_dtype, cache=layer_cache, pos=pos)
            return y, new_cache

        x, new_cache = jax.lax.scan(body, x, (seg_params, seg_cache))
        new_caches.append(new_cache)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params, cfg, x)[:, 0]
    return logits, new_caches


def prefill(params, cfg: ModelConfig, tokens, positions=None, cache_dtype=jnp.bfloat16,
            max_len: int | None = None):
    """Full-sequence prefill; returns (last-token logits, caches filled to S)."""
    compute_dtype = dt(cfg.compute_dtype)
    b, s = tokens.shape
    max_len = max_len or s
    x = embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, "model", None)
    rope_cs = _rope_tables(cfg, positions, b, s)
    caches = []
    for seg_params, (n, ttd_on) in zip(params["segments"], segment_plan(cfg)):
        specs = make_block_specs(cfg, ttd_on)

        def body(carry, layer_params, specs=specs):
            h = apply_norm(layer_params["ln1"], carry, cfg)
            a, kv = attn_full(layer_params, specs, cfg, h, rope_cs, compute_dtype,
                              return_kv=True, residual=carry)
            y = a.astype(carry.dtype)
            h2 = apply_norm(layer_params["ln2"], y, cfg)
            if specs.moe is not None:
                m, _ = apply_moe(layer_params["moe"], h2, specs.moe, cfg, compute_dtype)
                y = y + m.astype(y.dtype)
            else:
                y = apply_mlp(layer_params["mlp"], h2, specs.mlp_d(), cfg,
                              compute_dtype, residual=y).astype(y.dtype)
            y = constrain(y, BATCH, "model", None)
            k, v = kv
            w = min(cfg.window, max_len) if cfg.window else max_len
            k_c, v_c, pos_c = _ring_from_prefill(k, v, s, w, cache_dtype)
            return y, {"k": k_c, "v": v_c, "pos": pos_c}

        x, cache = jax.lax.scan(body, x, seg_params)
        caches.append(cache)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params, cfg, x[:, -1:])[:, 0]
    return logits, caches


def _ring_from_prefill(k, v, s, w, cache_dtype):
    """Pack the last ``w`` prefilled KVs into ring layout (slot = pos % w)."""
    b, _, hkv, dh = k.shape
    if s <= w:
        pad = w - s
        k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cache_dtype)
        v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cache_dtype)
        pos_c = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                                 jnp.full((pad,), -1, jnp.int32)])
        return k_c, v_c, pos_c
    # keep positions [s-w, s): position p lives at slot p % w
    tail_pos = jnp.arange(s - w, s, dtype=jnp.int32)  # positions kept
    slots = tail_pos % w
    k_tail = k[:, -w:].astype(cache_dtype)
    v_tail = v[:, -w:].astype(cache_dtype)
    k_c = jnp.zeros((b, w, hkv, dh), cache_dtype).at[:, slots].set(k_tail)
    v_c = jnp.zeros((b, w, hkv, dh), cache_dtype).at[:, slots].set(v_tail)
    pos_c = jnp.zeros((w,), jnp.int32).at[slots].set(tail_pos)
    return k_c, v_c, pos_c


# ---------------------------------------------------------------------------
# Paged-cache serving path (DESIGN.md §6).  Decode takes *per-sequence*
# positions — ragged batches decode in one call, unlike the ring path whose
# shared scalar ``pos`` forces the engine to group slots by position.
# ---------------------------------------------------------------------------
def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     cache_dtype=jnp.bfloat16):
    """Stacked per-layer paged K/V block pools (block 0 = reserved null).

    ``cache_dtype`` may be jnp.int8, in which case per-(block-slot, head)
    scale tables ride alongside the quantized values.
    """
    quantized = cache_dtype == jnp.int8

    def one(n):
        shape = (n, num_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
        c = {"k": jnp.zeros(shape, cache_dtype), "v": jnp.zeros(shape, cache_dtype)}
        if quantized:
            c["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            c["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        return c

    return [one(n) for n, _ in segment_plan(cfg)]


def _paged_rope(cfg: ModelConfig, positions):
    """Per-sequence rope tables; padding positions (-1) clamp to 0 (their
    outputs are masked/ignored downstream)."""
    if cfg.pos_type != "rope":
        if cfg.pos_type == "none":
            return None
        raise NotImplementedError(
            f"paged serving supports pos_type rope|none, not {cfg.pos_type!r}")
    return rope_angles(jnp.maximum(positions, 0), cfg.head_dim, cfg.rope_theta,
                       cfg.partial_rotary)


def _paged_body(params, specs, cfg, x, rope_cs, cache, block_tables, positions,
                compute_dtype):
    h = apply_norm(params["ln1"], x, cfg)
    a, new_cache = attn_paged(params, specs, cfg, h, rope_cs, cache,
                              block_tables, positions, compute_dtype, residual=x)
    x = constrain(a.astype(x.dtype), BATCH, "model", None)
    h = apply_norm(params["ln2"], x, cfg)
    if specs.moe is not None:
        m, _ = apply_moe(params["moe"], h, specs.moe, cfg, compute_dtype)
        x = x + m.astype(x.dtype)
    else:
        x = apply_mlp(params["mlp"], h, specs.mlp_d(), cfg, compute_dtype,
                      residual=x).astype(x.dtype)
    return constrain(x, BATCH, "model", None), new_cache


def _paged_stack(params, cfg: ModelConfig, caches, x, rope_cs, block_tables,
                 positions, compute_dtype):
    new_caches = []
    for seg_params, seg_cache, (n, ttd_on) in zip(params["segments"], caches,
                                                  segment_plan(cfg)):
        specs = make_block_specs(cfg, ttd_on)

        def body(carry, xs, specs=specs):
            layer_params, layer_cache = xs
            return _paged_body(layer_params, specs, cfg, carry, rope_cs,
                               layer_cache, block_tables, positions,
                               compute_dtype)

        x, new_cache = jax.lax.scan(body, x, (seg_params, seg_cache))
        new_caches.append(new_cache)
    return apply_norm(params["final_norm"], x, cfg), new_caches


def decode_step_paged(params, cfg: ModelConfig, caches, tokens, block_tables,
                      positions):
    """One decode tick against the paged cache.

    tokens: (B, 1); positions: (B,) absolute position of each new token
    (``-1`` = inactive row: its write lands in the null block and its logits
    are garbage the scheduler ignores).  Returns logits (B, V) f32 and the
    updated caches.
    """
    compute_dtype = dt(cfg.compute_dtype)
    x = embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, None, None)
    pos2 = positions[:, None].astype(jnp.int32)
    rope_cs = _paged_rope(cfg, pos2)
    x, new_caches = _paged_stack(params, cfg, caches, x, rope_cs, block_tables,
                                 pos2, compute_dtype)
    return logits_from_hidden(params, cfg, x)[:, 0], new_caches


def prefill_paged_chunk(params, cfg: ModelConfig, caches, tokens, block_tables,
                        positions):
    """One chunk of batched prefill, writing K/V straight into paged blocks.

    tokens: (B, C); positions: (B, C) absolute positions (``-1`` = padding —
    prompts shorter than the chunk grid, or rows with no prompt);
    block_tables: (B, W), row *i* the table of the sequence row *i*
    prefills.  Rows need not be decode slots: a row's slot matters only
    through its table, so a tile of just the admitted sequences computes
    the same as the (slots, C) tile.  Earlier chunks must already be
    written (the serve driver ``serve.steps.chunked_prefill`` guarantees
    order).  Returns logits (B, C, V) f32 for *every* chunk position — the
    driver picks each sequence's last-real-token row — and updated caches.
    """
    compute_dtype = dt(cfg.compute_dtype)
    x = embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, "model", None)
    rope_cs = _paged_rope(cfg, positions.astype(jnp.int32))
    x, new_caches = _paged_stack(params, cfg, caches, x, rope_cs, block_tables,
                                 positions.astype(jnp.int32), compute_dtype)
    return logits_from_hidden(params, cfg, x), new_caches


# ---------------------------------------------------------------------------
# Ring-cache serving path (session API, DESIGN.md §7).  Same position
# conventions as the paged path — per-sequence absolute positions, ``-1`` =
# inactive — but K/V live in per-slot rings of ``window + chunk`` entries
# instead of shared block pools.  This is the constant-footprint backend for
# sliding-window attention (paged block pools cannot express SWA eviction).
# ---------------------------------------------------------------------------
def ring_width(cfg: ModelConfig, max_len: int, chunk: int) -> int:
    """Per-slot ring entries: the visible window plus the widest same-call
    write (so a chunk write never evicts a key still visible to its own
    earliest query); full attention keeps the whole ``max_len``."""
    if cfg.window:
        return min(cfg.window, max_len) + chunk
    return max_len


def init_ring_cache(cfg: ModelConfig, batch: int, max_len: int, chunk: int,
                    cache_dtype=jnp.bfloat16):
    """Stacked per-layer per-slot ring caches with per-sequence positions.
    int8 rings carry per-(entry, head) f32 scale tables next to the payload
    (``ring_kv_update`` writes them; the prefill kernel dequantizes
    in-tile)."""
    wr = ring_width(cfg, max_len, chunk)
    int8 = jnp.dtype(cache_dtype) == jnp.int8

    def one(n):
        c = {
            "k": jnp.zeros((n, batch, wr, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
            "v": jnp.zeros((n, batch, wr, cfg.n_kv_heads, cfg.head_dim), cache_dtype),
            "pos": jnp.full((n, batch, wr), -1, jnp.int32),
        }
        if int8:
            c["k_scale"] = jnp.zeros((n, batch, wr, cfg.n_kv_heads), jnp.float32)
            c["v_scale"] = jnp.zeros((n, batch, wr, cfg.n_kv_heads), jnp.float32)
        return c

    return [one(n) for n, _ in segment_plan(cfg)]


def attn_ring(params, specs, cfg: ModelConfig, x, rope_cs, cache, positions,
              compute_dtype, residual=None):
    """Attention against a per-slot ring cache (write-then-attend).

    cache: one layer's ``{"k","v","pos"}`` rings; positions: (B, S) absolute
    positions (``-1`` = padding, write dropped / query masked).  Both chunked
    prefill (S > 1) and ragged decode (S == 1) run the streaming kernel via
    ``kernels.dispatch.prefill_attention`` (ring layout: the ring's ``pos``
    array is the kernel's ``kpos`` operand).
    """
    b, s, _ = x.shape
    q, k, v = _qkv(params, specs, cfg, x, rope_cs, compute_dtype)
    new_cache = ring_kv_update(cache, k, v, positions)
    o = dispatch.prefill_attention(q, positions, k=new_cache["k"],
                                   v=new_cache["v"], kpos=new_cache["pos"],
                                   window=cfg.window,
                                   k_scale=new_cache.get("k_scale"),
                                   v_scale=new_cache.get("v_scale"))
    o = constrain(o.astype(compute_dtype), BATCH, None, "model", None)
    o = apply_linear(params["attn"]["wo"], o.reshape(b, s, cfg.q_dim),
                     specs.attn_d()["wo"], compute_dtype, residual=residual)
    return o, new_cache


def _ring_stack(params, cfg: ModelConfig, caches, x, rope_cs, positions,
                compute_dtype):
    new_caches = []
    for seg_params, seg_cache, (n, ttd_on) in zip(params["segments"], caches,
                                                  segment_plan(cfg)):
        specs = make_block_specs(cfg, ttd_on)

        def body(carry, xs, specs=specs):
            layer_params, layer_cache = xs
            h = apply_norm(layer_params["ln1"], carry, cfg)
            a, new_cache = attn_ring(layer_params, specs, cfg, h, rope_cs,
                                     layer_cache, positions, compute_dtype,
                                     residual=carry)
            y = constrain(a.astype(carry.dtype), BATCH, "model", None)
            h = apply_norm(layer_params["ln2"], y, cfg)
            if specs.moe is not None:
                m, _ = apply_moe(layer_params["moe"], h, specs.moe, cfg, compute_dtype)
                y = y + m.astype(y.dtype)
            else:
                y = apply_mlp(layer_params["mlp"], h, specs.mlp_d(), cfg,
                              compute_dtype, residual=y).astype(y.dtype)
            return constrain(y, BATCH, "model", None), new_cache

        x, new_cache = jax.lax.scan(body, x, (seg_params, seg_cache))
        new_caches.append(new_cache)
    return apply_norm(params["final_norm"], x, cfg), new_caches


def prefill_ring_chunk(params, cfg: ModelConfig, caches, tokens, positions):
    """One chunk of batched prefill into per-slot rings.

    tokens: (B, C); positions: (B, C) absolute (``-1`` = padding).  Returns
    logits (B, C, V) f32 for every chunk position and the updated caches.
    """
    compute_dtype = dt(cfg.compute_dtype)
    x = embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, "model", None)
    rope_cs = _paged_rope(cfg, positions.astype(jnp.int32))
    x, new_caches = _ring_stack(params, cfg, caches, x, rope_cs,
                                positions.astype(jnp.int32), compute_dtype)
    return logits_from_hidden(params, cfg, x), new_caches


def decode_step_ring(params, cfg: ModelConfig, caches, tokens, positions):
    """One ragged decode tick against per-slot rings.

    tokens: (B, 1); positions: (B,) absolute position of each new token
    (``-1`` = inactive row).  Returns logits (B, V) f32 and updated caches.
    """
    compute_dtype = dt(cfg.compute_dtype)
    x = embed_lookup(params["embed"], tokens, compute_dtype, cfg)
    x = constrain(x, BATCH, None, None)
    pos2 = positions[:, None].astype(jnp.int32)
    rope_cs = _paged_rope(cfg, pos2)
    x, new_caches = _ring_stack(params, cfg, caches, x, rope_cs, pos2,
                                compute_dtype)
    return logits_from_hidden(params, cfg, x)[:, 0], new_caches


# ---------------------------------------------------------------------------
# Specs tree (mirrors init_lm params structure; used by core.compress)
# ---------------------------------------------------------------------------
def specs_tree(cfg: ModelConfig):
    segs = []
    for n, ttd_on in segment_plan(cfg):
        sp = make_block_specs(cfg, ttd_on)
        seg = {"ln1": None, "ln2": None, "attn": {nm: s for nm, s in sp.attn}}
        if sp.moe is not None:
            seg["moe"] = {"router": sp.moe["router"],
                          "experts": dict(sp.moe["expert"])}
        else:
            seg["mlp"] = sp.mlp_d()
        segs.append(seg)
    tree = {"embed": embed_spec(cfg), "segments": segs, "final_norm": None}
    if not cfg.tie_embeddings:
        tree["head"] = None
    return tree
