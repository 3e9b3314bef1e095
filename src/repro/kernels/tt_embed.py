"""Fused gathered-row TT embedding kernel (TensorGPT-style vocab-axis TT).

The embedding table (V, D) is stored as a TT whose (M, N) weight has the
vocab on the output axis (M = V), so looking a token up never reconstructs
the table.  Per token-id the kernel:

  1. splits the id into its big-endian ``out_modes`` digits (i_1..i_d);
  2. gathers digit i_k's ``(r0, n_k, r1)`` column block of core matrix C_k
     for the whole token tile with one one-hot matmul (MXU-friendly — no
     dynamic gather inside the kernel body);
  3. chains the per-token slices left-to-right with batched dot_generals,
     exactly the ``tt_linear`` stage contraction restricted to one row.

Grid is 1-D over token tiles; all cores are pinned whole in VMEM (they are
the compressed representation — a few KB).  Ids follow the dense path's
``jnp.take`` semantics for padding: negative ids wrap once (``-1`` is row
``V - 1``), anything else clamps into range.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.ttd import TTSpec

VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def pick_block_t(spec: TTSpec, n_tokens: int, dtype_bytes: int = 4) -> int:
    """Largest power-of-two token tile whose working set fits the budget."""
    per_token = (
        spec.n_in * max(spec.ranks)  # widest running row chunk
        + max(spec.ranks[k] * spec.in_modes[k] * spec.ranks[k + 1]
              for k in range(spec.d))  # largest per-core selection
        + max(spec.out_modes)  # one-hot row
    ) * dtype_bytes
    cores_bytes = spec.n_params() * dtype_bytes
    bt = 8
    while bt * 2 <= n_tokens and (bt * 2) * per_token + cores_bytes <= VMEM_BUDGET_BYTES:
        bt *= 2
    return bt


def _kernel(ids_ref, *refs, spec: TTSpec, block_t: int):
    cores = [refs[k][...] for k in range(spec.d)]
    out_ref = refs[-1]
    ids = ids_ref[...].reshape(block_t)
    ids = jnp.clip(jnp.where(ids < 0, ids + spec.n_out, ids), 0, spec.n_out - 1)
    m = spec.out_modes
    p = None
    for k in range(spec.d):
        stride = math.prod(m[k + 1:])
        digit = (ids // stride) % m[k]  # (T,)
        r0, r1 = spec.ranks[k], spec.ranks[k + 1]
        n_k = spec.in_modes[k]
        onehot = (digit[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (block_t, m[k]), 1)).astype(jnp.float32)
        # C_k rows are (r0, n_k), columns (m_k, r1): one matmul gathers the
        # digit's (r0, n_k, r1) column block for every token in the tile
        c = cores[k].astype(jnp.float32).reshape(r0, n_k, m[k], r1)
        c = c.transpose(2, 0, 1, 3).reshape(m[k], r0 * n_k * r1)
        sel = jax.lax.dot_general(onehot, c, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        sel = sel.reshape(block_t, r0, n_k * r1)
        if p is None:
            p = sel.reshape(block_t, n_k, r1)  # r0 == 1 on the first core
        else:
            # (T, X, r0) x (T, r0, n_k*r1) batched over the token tile
            p = jax.lax.dot_general(p, sel, (((2,), (1,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32)
            p = p.reshape(block_t, -1, r1)
    out_ref[...] = p.reshape(block_t, spec.n_in)


def tt_embed_pallas(ids: jax.Array, cores: list[jax.Array], spec: TTSpec, *,
                    block_t: int | None = None,
                    interpret: bool) -> jax.Array:
    """ids (T,) int32 -> (T, D) f32 rows of the TT-described (V, D) table."""
    (t,) = ids.shape
    bt = block_t or pick_block_t(spec, max(t, 8))
    pad = (-t) % bt
    ids32 = jnp.asarray(ids, jnp.int32)
    if pad:
        ids32 = jnp.pad(ids32, (0, pad))
    in_specs = [pl.BlockSpec((bt,), lambda i: (i,))]
    in_specs += [pl.BlockSpec(c.shape, lambda i, nd=c.ndim: tuple([0] * nd))
                 for c in cores]
    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, block_t=bt),
        grid=(ids32.shape[0] // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, spec.n_in), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ids32.shape[0], spec.n_in), jnp.float32),
        name="tt_embed",
        interpret=interpret,
    )(ids32, *cores)
    return out[:t] if pad else out
