"""w4a16 matmul Pallas TPU kernel (paper's FP16×INT4 DSP-shared PEs, §IV).

TPU adaptation: the DSP trick packs two INT4 weights through one 27×18
multiplier; the MXU has no sub-8-bit mode, so we keep the *intent* — halve
weight HBM traffic — by shipping weights as packed nibbles (uint8, 2/byte)
plus per-group scales, and unpacking + dequantizing *inside* the kernel after
the HBM->VMEM copy.  The dequantized tile lives only in VMEM; the matmul runs
at full bf16 MXU throughput.

Grid tiles (tokens × out-features); the contraction dim K is kept whole in
VMEM (our layer K ≤ 16384 at block sizes 128/256 stays under budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .epilogue import apply_epilogue


def _kernel(x_ref, qw_ref, sc_ref, *refs, group: int, has_scale: bool,
            has_bias: bool, has_res: bool, activation: str | None, out_dtype):
    x = x_ref[...]  # (bb, K)
    qw = qw_ref[...]  # (bm, K//2) uint8 packed
    sc = sc_ref[...]  # (bm, K//group)
    rest = list(refs[:-1])
    out_ref = refs[-1]
    bm, kh = qw.shape
    k = kh * 2
    lo = (qw & 0x0F).astype(jnp.int8)
    hi = ((qw >> 4) & 0x0F).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    w = jnp.stack([lo, hi], axis=-1).reshape(bm, k)  # interleave nibbles
    w = w.reshape(bm, k // group, group).astype(jnp.float32) * \
        sc[..., None].astype(jnp.float32)
    w = w.reshape(bm, k)
    y = jax.lax.dot_general(x.astype(jnp.float32), w,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    i = 0
    ep_scale = ep_bias = ep_res = None
    if has_scale:
        ep_scale, i = rest[i][...], i + 1
    if has_bias:
        ep_bias, i = rest[i][...], i + 1
    if has_res:
        ep_res = rest[i][...]
    y = apply_epilogue(y, scale=ep_scale, bias=ep_bias, residual=ep_res,
                       activation=activation)
    out_ref[...] = y.astype(out_dtype)


def int4_matmul_pallas(x: jax.Array, qweight: jax.Array, scales: jax.Array, *,
                       group: int = 128, block_b: int = 128, block_m: int = 128,
                       scale: jax.Array | None = None,
                       bias: jax.Array | None = None,
                       residual: jax.Array | None = None,
                       activation: str | None = None,
                       interpret: bool) -> jax.Array:
    """y = act(x @ dequant(qweight)^T [* scale] [+ bias]) [+ residual].

    x: (B, K) -> (B, M).  The epilogue operands mirror the TT kernel's
    fused TTDLinear-BN(-Res) post-ops (scale/bias: (M,), residual: (B, M)).
    """
    b, k = x.shape
    m = qweight.shape[0]
    assert qweight.shape == (m, k // 2), (qweight.shape, (m, k // 2))
    assert scales.shape == (m, k // group)

    bb = min(block_b, _pow2_floor(b))
    bm = min(block_m, _pow2_floor(m))
    pad_b, pad_m = (-b) % bb, (-m) % bm
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, pad_b), (0, 0)))
    if pad_m:
        qweight = jnp.pad(qweight, ((0, pad_m), (0, 0)))
        scales = jnp.pad(scales, ((0, pad_m), (0, 0)))
        scale = jnp.pad(scale, (0, pad_m)) if scale is not None else None
        bias = jnp.pad(bias, (0, pad_m)) if bias is not None else None
        if residual is not None:
            residual = jnp.pad(residual, ((0, 0), (0, pad_m)))
    nb, nm = x.shape[0] // bb, qweight.shape[0] // bm

    in_specs = [
        pl.BlockSpec((bb, k), lambda i, j: (i, 0)),
        pl.BlockSpec((bm, k // 2), lambda i, j: (j, 0)),
        pl.BlockSpec((bm, k // group), lambda i, j: (j, 0)),
    ]
    extra = []
    for vec in (scale, bias):
        if vec is not None:
            extra.append(vec)
            in_specs.append(pl.BlockSpec((bm,), lambda i, j: (j,)))
    if residual is not None:
        extra.append(residual)
        in_specs.append(pl.BlockSpec((bb, bm), lambda i, j: (i, j)))

    out = pl.pallas_call(
        functools.partial(_kernel, group=group, has_scale=scale is not None,
                          has_bias=bias is not None, has_res=residual is not None,
                          activation=activation, out_dtype=x.dtype),
        grid=(nb, nm),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], qweight.shape[0]), x.dtype),
        name="int4_matmul",
        interpret=interpret,
    )(x, qweight, scales, *extra)
    return out[:b, :m] if (pad_b or pad_m) else out


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p
