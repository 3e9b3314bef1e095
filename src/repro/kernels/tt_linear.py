"""Fused multi-stage TT-linear Pallas TPU kernel.

TPU adaptation of the paper's GVSA TTD dataflow (§III.C):

  * All d TT cores are pinned in VMEM for the kernel's lifetime (they total
    ~35-45 KB per layer after compression — the whole point of TTD).  This is
    the analogue of GVSA's weight-stationary PEs.
  * The staged contraction P_0 -> P_1 -> … -> P_d (paper Eq. 4) runs entirely
    in VMEM/VREGs; the inter-stage *reorder* (paper: hidden in the ping-pong
    buffer write/read pattern) never touches HBM.
  * Per-token HBM traffic is exactly N + M elements (input + output) plus the
    one-time core fetch: the memory-bound linear layer becomes bandwidth-
    optimal (paper's roofline argument, §I).
  * Optional fused epilogue: ``act(y*scale + bias) (+ residual)`` — the
    paper's TTDLinear-BN(-Res) operator fusion; every operand is independent
    (bias-only gives the plain biased linear).  Shared semantics live in
    ``repro.kernels.epilogue``.

Layout.  Inside the kernel the token tile lives in the *lane* (last)
dimension: the input tile is transposed once to (N, block_b), and every
stage holds its intermediate as (T, r·n, block_b).  A stage is one batched
MXU matmul ``C_kᵀ (m·r′, r·n) @ P[t] (r·n, block_b)``, and the reorder
between stages only splits, permutes and merges the two leading dimensions.
No reorder ever splits the lane dimension into small mode sizes, which the
TPU's vector layouts cannot express.  The cores are pre-permuted to the
kernel's layout by :func:`kernel_cores` (rows n-major, so merging the next
in-mode into the rank dimension keeps the rank-sized sublane tiles whole).
The last stage writes its (m_d, block_b) slabs into an (M, block_b) VMEM
scratch at row offset ``t·m_d``; that is the one place a mode size such as
107 lands in the sublane dimension, and a row-offset store handles it where a
reshape would not.  The scratch is transposed back to (block_b, M) for the
epilogue.

Intermediates are stored in the input dtype between stages and every
contraction accumulates in f32 — the same rounding points as the pure-JAX
staged reference (``core.tt_linear``).  ``block_b`` is chosen so the largest
intermediate fits a VMEM budget.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.ttd import TTSpec
from .epilogue import apply_epilogue

VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # what pick_block_b plans for
# Mosaic's own relayout copies come on top of the planned working set; v5e
# has 128 MiB of VMEM per core, so the scoped limit leaves them room.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
MIN_TILED_BLOCK = 8  # a block that does not span all rows must fill whole sublane tiles


def pick_block_b(spec: TTSpec, batch: int, dtype_bytes: int = 4) -> int:
    """Token block for a ``batch``-row call.

    A batch of at most ``MIN_TILED_BLOCK`` rows is one block spanning every
    row.  Otherwise the block is the largest power of two, at least
    ``MIN_TILED_BLOCK``, whose working set fits the VMEM budget: the
    double-buffered input, residual and output tiles, the f32 output scratch
    and its transpose, and a stage's input, f32 product and reordered copy.
    """
    if batch <= MIN_TILED_BLOCK:
        return batch
    per_token = (2 * (spec.n_in + 2 * spec.n_out) * dtype_bytes
                 + 2 * spec.n_out * 4
                 + spec.max_intermediate() * (4 + 2 * dtype_bytes))
    cores = spec.n_params() * dtype_bytes
    bb = MIN_TILED_BLOCK
    while bb * 2 <= batch and (bb * 2) * per_token + cores <= VMEM_BUDGET_BYTES:
        bb *= 2
    return bb


def kernel_cores(cores, spec: TTSpec, dtype) -> list[jax.Array]:
    """Matrix cores C_k (r·n, m·r′), rows r-major, -> the kernel's LHS layout
    (m·r′, n·r): transposed, with the contraction rows reordered n-major."""
    out = []
    for k, c in enumerate(cores):
        r, n = spec.ranks[k], spec.in_modes[k]
        a = c.shape[1]
        out.append(jnp.asarray(c, dtype).reshape(r, n, a).transpose(2, 1, 0)
                   .reshape(a, n * r))
    return out


def _stage_contract(xt, cores, spec: TTSpec, y_ref):
    """Eq.-4 staged contraction of an (N, bt) token-in-lanes tile into the
    (M, bt) f32 scratch ``y_ref``."""
    n, m, d = spec.in_modes, spec.out_modes, spec.d
    bt = xt.shape[-1]
    store = xt.dtype
    # (i_0, T_0, bt) -> (T_0, i_0, bt) with T_0 = (i_1, …, i_{d-1})
    p = xt.reshape(n[0], math.prod(n[1:]), bt).transpose(1, 0, 2)
    m_prod = 1
    for k in range(d - 1):
        p = jnp.einsum("ac,tcb->tab", cores[k], p,
                       preferred_element_type=jnp.float32).astype(store)
        # the "ping-pong reorder": (n_{k+1}, NR, MP, m_k, r, bt)
        #                        -> (NR, MP, m_k, n_{k+1}·r, bt)
        nr, r = math.prod(n[k + 2:]), spec.ranks[k + 1]
        p = p.reshape(n[k + 1], nr, m_prod, m[k], r, bt)
        p = p.transpose(1, 2, 3, 0, 4, 5)
        m_prod *= m[k]
        p = p.reshape(nr * m_prod, n[k + 1] * r, bt)
    last = cores[d - 1]
    for t in range(m_prod):  # (m_d, bt) slab of output rows t·m_d … t·m_d+m_d
        y_ref[t * m[d - 1]:(t + 1) * m[d - 1], :] = jnp.dot(
            last, p[t], preferred_element_type=jnp.float32)


def _kernel(x_ref, *refs, spec: TTSpec, has_scale: bool, has_bias: bool,
            has_res: bool, activation: str | None):
    d = spec.d
    cores = [refs[k][...] for k in range(d)]
    rest = list(refs[d:-2])
    out_ref, y_ref = refs[-2], refs[-1]
    _stage_contract(x_ref[...].T, cores, spec, y_ref)
    y = y_ref[...].T
    i = 0
    scale = bias = res = None
    if has_scale:
        scale, i = rest[i][...], i + 1
    if has_bias:
        bias, i = rest[i][...], i + 1
    if has_res:
        res = rest[i][...]
    y = apply_epilogue(y, scale=scale, bias=bias, residual=res,
                       activation=activation)
    out_ref[...] = y.astype(out_ref.dtype)


def tt_linear_pallas(x: jax.Array, cores: list[jax.Array], spec: TTSpec, *,
                     scale: jax.Array | None = None,
                     bias: jax.Array | None = None,
                     residual: jax.Array | None = None,
                     activation: str | None = None,
                     block_b: int | None = None,
                     interpret: bool) -> jax.Array:
    """y = act(TTLinear(x) [* scale] [+ bias]) [+ residual];  (B, N) -> (B, M).

    Any epilogue operand may be passed independently (bias without scale is
    the plain ``y + b`` linear; scale+bias is the paper's TTDLinear-BN).
    ``interpret=True`` runs the kernel body in the Pallas interpreter (the
    CPU test path); ``interpret=False`` lowers it through Mosaic for a TPU.
    """
    b, n_in = x.shape
    assert n_in == spec.n_in, (n_in, spec)

    bb = block_b or pick_block_b(spec, b, x.dtype.itemsize)
    pad = (-b) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, pad), (0, 0)))
    nb = x.shape[0] // bb
    kcores = kernel_cores(cores, spec, x.dtype)

    in_specs = [pl.BlockSpec((bb, spec.n_in), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in kcores]
    extra = []
    for vec in (scale, bias):
        if vec is not None:
            extra.append(vec.reshape(1, spec.n_out))
            in_specs.append(pl.BlockSpec((1, spec.n_out), lambda i: (0, 0)))
    if residual is not None:
        extra.append(residual)
        in_specs.append(pl.BlockSpec((bb, spec.n_out), lambda i: (i, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, has_scale=scale is not None,
                          has_bias=bias is not None,
                          has_res=residual is not None, activation=activation),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, spec.n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], spec.n_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((spec.n_out, bb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="tt_linear",
        interpret=interpret,
    )(x, *kcores, *extra)
    return out[:b] if pad else out
