"""Ragged chunked-prefill flash-attention Pallas kernel.

The paper's headline serving number is *first-token delay*, which is decided
by the prefill path.  PR 3 fused decode (one query per sequence), but chunked
prefill still ran the pure-jnp gather oracle: every layer materialized the
whole ``(B, W*BS, Hkv, Dh)`` f32 gathered context in HBM and computed dense
``(Sq × K)`` scores including idle rows.  This kernel closes that gap — the
last fork between "kernel-accelerated decode" and "oracle-math prefill".

One (sequence, tile of query tokens) streams K/V tiles through the flash
online-softmax recurrence,
with GQA head grouping and causal + sliding-window masking driven by
per-sequence absolute positions (``-1`` = padding → zero output).  Two cache
layouts share the kernel body:

* **paged** — K/V live in shared block pools addressed through a per-sequence
  block table; K positions are implicit (gathered index *i* holds absolute
  position *i*), tiles are the ``block_size``-wide blocks, and the visible
  block count is the tile's max query position rounded up to blocks, so a
  tile never reads beyond the blocks its sequence actually occupies
  (all-idle tiles compute nothing).  int8 pools dequantize per-(block-slot, head)
  scales in-tile, fused with the score matmul.
* **ring** — K/V are per-slot rings with an explicit ``kpos`` operand
  (``-1`` = empty entry); tiles stream over the ring width, and the mask is
  position-driven (causal, ``kpos >= 0``, sliding window), so SWA families
  (mixtral, griffin's attention layers) prefill through the same kernel.

Grid: ``(seq, q-tile, kv-tile)``.  Like ``kernels/paged_attention.py``, the
paged layout scalar-prefetches the block table (and each q tile's visible
block count) into SMEM, so the K/V BlockSpec index_map resolves the physical
block id and the pipeline DMAs one (block_size, Hkv, Dh) tile from HBM per
grid step; steps past a tile's last visible block re-map to it (no new
fetch) and skip their compute.  The ring layout streams its per-slot ring
through the same grid with a plain index_map.  The online-softmax state for
one (seq, q-tile) lives in VMEM scratch across its kv-tile steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(*refs, paged: bool, kv_tile: int, n_kv_heads: int, window: int,
            sm_scale: float, quantized: bool):
    if paged:  # the block table itself is read by the index_maps
        _, nblk_ref, q_ref, qpos_ref, k_ref, v_ref, *refs = refs
        kpos_ref = None
    else:
        q_ref, qpos_ref, kpos_ref, k_ref, v_ref, *refs = refs
    if quantized:
        ks_ref, vs_ref, *refs = refs
    out_ref, m_sc, l_sc, acc_sc = refs
    i, jq, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def _step():
        qt, h, dh = q_ref.shape[1:]
        g = h // n_kv_heads
        # rows are (query, head-in-group); each row's absolute position
        qrow = jnp.broadcast_to(qpos_ref[0][:, None, :], (qt, g, 1)
                                ).reshape(qt * g, 1)
        if paged:
            kpos = t * kv_tile + jax.lax.broadcasted_iota(
                jnp.int32, (1, kv_tile), 1)
            valid = kpos <= qrow  # causal + ragged block
        else:
            kpos = kpos_ref[0]  # (1, KT); -1 = empty ring entry
            valid = (kpos >= 0) & (kpos <= qrow)
        valid &= qrow >= 0
        if window > 0:
            valid &= qrow - kpos < window
        for hk in range(n_kv_heads):
            heads = slice(hk * g, (hk + 1) * g)
            qh = (q_ref[0, :, heads, :].astype(jnp.float32) * sm_scale
                  ).reshape(qt * g, dh)
            kb = k_ref[0, :, hk, :].astype(jnp.float32)  # (KT, Dh)
            vb = v_ref[0, :, hk, :].astype(jnp.float32)
            if quantized:
                kb = kb * ks_ref[0, :, hk:hk + 1]
                vb = vb * vs_ref[0, :, hk:hk + 1]
            s = jax.lax.dot_general(qh, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)  # (QT*G, KT)
            m_prev, l_prev = m_sc[hk], l_sc[hk]  # (QT*G, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new) * valid
            l_sc[hk] = l_prev * corr + p.sum(-1, keepdims=True)
            acc_sc[hk] = acc_sc[hk] * corr + jnp.dot(
                p, vb, preferred_element_type=jnp.float32)
            m_sc[hk] = m_new

    if paged:
        pl.when(t < nblk_ref[i * pl.num_programs(1) + jq])(_step)
    else:
        _step()

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        qt, h, dh = q_ref.shape[1:]
        g = h // n_kv_heads
        for hk in range(n_kv_heads):
            l = l_sc[hk]
            out = jnp.where(l > 0, acc_sc[hk] / jnp.maximum(l, 1e-30), 0.0)
            out_ref[0, :, hk * g:(hk + 1) * g, :] = out.reshape(
                qt, g, dh).astype(out_ref.dtype)


def prefill_attention_pallas(q: jax.Array, qpos: jax.Array, *,
                             cache: dict | None = None,
                             block_tables: jax.Array | None = None,
                             k: jax.Array | None = None,
                             v: jax.Array | None = None,
                             kpos: jax.Array | None = None,
                             window: int = 0, sm_scale: float | None = None,
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None,
                             q_tile: int = 64, kv_tile: int = 128,
                             interpret: bool) -> jax.Array:
    """Chunked-prefill attention over a paged pool or per-slot rings.

    q: (B, Sq, H, Dh); qpos: (B, Sq) int32 absolute query positions (``-1`` =
    padding row → zero output).  Exactly one layout:

    * paged — ``cache``: ``{"k","v": (NB, BS, Hkv, Dh)}`` plus
      ``k_scale``/``v_scale`` ``(NB, BS, Hkv)`` for int8 pools;
      ``block_tables``: (B, W) int32 ordered logical→physical ids.
    * ring — ``k``/``v``: (B, WR, Hkv, Dh); ``kpos``: (B, WR) int32 absolute
      key positions, ``-1`` = empty entry; int8 rings carry per-entry-per-head
      f32 ``k_scale``/``v_scale`` (B, WR, Hkv) dequantized in-tile.

    The chunk's own K/V must already be written (write-then-attend, as both
    ``paged_kv_update`` and ``ring_kv_update`` guarantee).  Returns
    (B, Sq, H, Dh) in ``q.dtype``.  ``interpret=True`` runs the kernel in
    the Pallas interpreter (the CPU test path); serving goes through
    ``kernels.dispatch.prefill_attention``.
    """
    paged = cache is not None
    b, sq, h, dh = q.shape
    sm_scale = sm_scale or (1.0 / math.sqrt(dh))
    qt = min(q_tile, sq)
    pad_q = (-sq) % qt
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad_q)), constant_values=-1)
    qpos = qpos.astype(jnp.int32)
    nqt = q.shape[1] // qt

    if paged:
        _, bs, hkv, _ = cache["k"].shape
        w = block_tables.shape[1]
        quantized = "k_scale" in cache
        kv_t, n_t = bs, w
        # blocks each q tile can see: its max position rounded up (0 if idle)
        qmax = qpos.reshape(b, nqt, qt).max(-1)
        nblk = (jnp.maximum(qmax + 1, 0) + bs - 1) // bs
        scalars = [block_tables.astype(jnp.int32).reshape(-1),
                   nblk.reshape(-1).astype(jnp.int32)]

        def kv_block(i, jq, t, bt, nb):
            last = jnp.maximum(nb[i * nqt + jq] - 1, 0)
            return bt[i * w + jnp.minimum(t, last)], 0, 0, 0

        def q_map(f):
            return lambda i, jq, t, bt, nb: f(i, jq, t)

        kv_specs = [pl.BlockSpec((1, bs, hkv, dh), kv_block)] * 2
        kv_args = [cache["k"], cache["v"]]
        if quantized:
            kv_specs += [pl.BlockSpec((1, bs, hkv),
                                      lambda *a: kv_block(*a)[:3])] * 2
            kv_args += [cache["k_scale"].astype(jnp.float32),
                        cache["v_scale"].astype(jnp.float32)]
    else:
        if k is None or v is None or kpos is None:
            raise ValueError("ring layout needs k, v and kpos")
        skv, hkv = k.shape[1], k.shape[2]
        quantized = k_scale is not None
        kv_t = min(kv_tile, skv)
        pad_k = (-skv) % kv_t
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            kpos = jnp.pad(kpos, ((0, 0), (0, pad_k)), constant_values=-1)
            if quantized:
                k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_k), (0, 0)))
                v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_k), (0, 0)))
        n_t = k.shape[1] // kv_t
        scalars = []

        def q_map(f):
            return f

        kv_specs = [pl.BlockSpec((1, 1, kv_t), lambda i, jq, t: (i, 0, t))]
        kv_specs += [pl.BlockSpec((1, kv_t, hkv, dh),
                                  lambda i, jq, t: (i, t, 0, 0))] * 2
        kv_args = [kpos.astype(jnp.int32)[:, None, :], k, v]
        if quantized:
            kv_specs += [pl.BlockSpec((1, kv_t, hkv),
                                      lambda i, jq, t: (i, t, 0))] * 2
            kv_args += [k_scale.astype(jnp.float32),
                        v_scale.astype(jnp.float32)]

    g = h // hkv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, nqt, n_t),
        in_specs=[pl.BlockSpec((1, qt, h, dh), q_map(lambda i, jq, t: (i, jq, 0, 0))),
                  pl.BlockSpec((1, qt, 1), q_map(lambda i, jq, t: (i, jq, 0)))]
        + kv_specs,
        out_specs=pl.BlockSpec((1, qt, h, dh), q_map(lambda i, jq, t: (i, jq, 0, 0))),
        scratch_shapes=[pltpu.VMEM((hkv, qt * g, 1), jnp.float32),
                        pltpu.VMEM((hkv, qt * g, 1), jnp.float32),
                        pltpu.VMEM((hkv, qt * g, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, paged=paged, kv_tile=kv_t, n_kv_heads=hkv,
                          window=window, sm_scale=sm_scale,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="prefill_attention",
        interpret=interpret,
    )(*scalars, q, qpos[:, :, None], *kv_args)
    return out[:, :sq] if pad_q else out
