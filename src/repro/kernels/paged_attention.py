"""Paged decode-attention Pallas kernel (one query token per sequence).

Serving decode is the shape the paper optimizes first-token-onward latency
for: every active sequence contributes exactly one query token per tick, and
its K/V context lives scattered across fixed-size blocks owned via a block
table (see ``serve/kv_cache.py``).  This kernel fuses the whole per-sequence
attention — block-table indirection, optional int8 dequant, online softmax,
GQA head grouping — into a single pass, so decode never materializes a
gathered (B, S, Hkv, Dh) context in HBM the way the pure-JAX reference
(``kernels/ref.py::paged_attention``) does.

Grid: ``(sequence, logical block)``.  The block table and the query
positions are scalar-prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``),
so the K/V BlockSpec index_map looks up the physical block id and the
pipeline DMAs exactly one (block_size, Hkv, Dh) K/V tile from HBM per grid
step — the pool itself never has to fit VMEM.  Steps past a sequence's last
occupied block re-map to that block (the pipeline skips the repeated fetch)
and skip their compute; the running (m, l, acc) online-softmax state lives in
VMEM scratch across a sequence's steps, and the ragged last block / empty
sequence cases fall out of the position mask.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _n_blocks(qpos, block_size: int):
    """Blocks a sequence whose newest token sits at ``qpos`` occupies."""
    return (jnp.maximum(qpos + 1, 0) + block_size - 1) // block_size


def _kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, *refs, block_size: int,
            n_kv_heads: int, sm_scale: float, quantized: bool):
    del bt_ref  # consumed by the index_maps
    if quantized:
        ks_ref, vs_ref, out_ref, m_sc, l_sc, acc_sc = refs
    else:
        out_ref, m_sc, l_sc, acc_sc = refs
    i, j = pl.program_id(0), pl.program_id(1)
    qpos = qpos_ref[i]  # -1 = inactive sequence

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j < _n_blocks(qpos, block_size))
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (H, Dh)
        g = q.shape[0] // n_kv_heads
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = kpos <= qpos  # causal + ragged-last-block mask, (1, BS)
        for h in range(n_kv_heads):
            kb = k_ref[0, :, h, :].astype(jnp.float32)  # (BS, Dh)
            vb = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                kb = kb * ks_ref[0, :, h:h + 1]
                vb = vb * vs_ref[0, :, h:h + 1]
            rows = slice(h * g, (h + 1) * g)
            s = jax.lax.dot_general(q[rows], kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)  # (G, BS)
            m_prev, l_prev = m_sc[rows], l_sc[rows]  # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new) * valid
            l_sc[rows] = l_prev * corr + p.sum(-1, keepdims=True)
            acc_sc[rows] = acc_sc[rows] * corr + jnp.dot(
                p, vb, preferred_element_type=jnp.float32)
            m_sc[rows] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_sc[...]
        out = jnp.where(l > 0, acc_sc[...] / jnp.maximum(l, 1e-30), 0.0)
        out_ref[0] = out.astype(out_ref.dtype)


def paged_attention_pallas(q: jax.Array, cache: dict, block_tables: jax.Array,
                           qpos: jax.Array, *, sm_scale: float | None = None,
                           interpret: bool) -> jax.Array:
    """Decode attention through a block table; one query token per sequence.

    q: (B, H, Dh); cache: ``{"k","v": (NB, BS, Hkv, Dh)}`` plus
    ``k_scale``/``v_scale`` ``(NB, BS, Hkv)`` when the cache dtype is int8;
    block_tables: (B, W) int32; qpos: (B,) int32 absolute position of each
    new token (its K/V already written), ``-1`` for inactive rows (output
    zeros).  Returns (B, H, Dh) in ``q.dtype``.

    ``interpret=True`` runs the kernel in the Pallas interpreter (the CPU
    test path); serving goes through ``kernels.dispatch.paged_attention``,
    which sets it from the backend policy (``pallas`` → compiled via Mosaic).
    """
    b, h, dh = q.shape
    _, bs, hkv, _ = cache["k"].shape
    w = block_tables.shape[1]
    quantized = "k_scale" in cache
    sm_scale = sm_scale or (1.0 / math.sqrt(dh))

    def kv_block(i, j, bt, qp):
        # past the last occupied block: stay on it (no new DMA, no compute)
        last = jnp.maximum(_n_blocks(qp[i], bs) - 1, 0)
        return bt[i * w + jnp.minimum(j, last)], 0, 0, 0

    def scale_block(i, j, bt, qp):
        return kv_block(i, j, bt, qp)[:3]

    in_specs = [
        pl.BlockSpec((1, h, dh), lambda i, j, bt, qp: (i, 0, 0)),
        pl.BlockSpec((1, bs, hkv, dh), kv_block),
        pl.BlockSpec((1, bs, hkv, dh), kv_block),
    ]
    args = [q, cache["k"], cache["v"]]
    if quantized:
        for nm in ("k_scale", "v_scale"):
            in_specs.append(pl.BlockSpec((1, bs, hkv), scale_block))
            args.append(cache[nm].astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, dh), lambda i, j, bt, qp: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, dh), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_size=bs, n_kv_heads=hkv,
                          sm_scale=sm_scale, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32).reshape(-1), qpos.astype(jnp.int32),
      *args)
