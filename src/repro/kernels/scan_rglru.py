"""Fused RG-LRU recurrent-scan Pallas kernel (griffin / recurrentgemma).

The RG-LRU recurrence ``h_t = a_t h_{t-1} + sqrt(1 - a_t²) (i_t ⊙ u_t)`` is
diagonal over the LRU width, so the natural kernel decomposition is
``(sequence, width-tile)``: each program owns one slot's slice of the state
and streams the chunk's token tiles through it, keeping ``h`` resident
on-chip for the whole call instead of round-tripping (B, S, W) operands per
scan step.

* **prefill** (S > 1) — grid ``(B, W/Wt)``.  Each program loads its
  (S, Wt) ``log_a``/``gx`` panes once, applies the position mask (``-1`` =
  padding → a = 1, input 0: the state passes through *bitwise* in the f32
  carry), folds ``h0`` in, then walks token tiles of width ``TT`` with a
  log-depth Hillis–Steele scan inside each tile and a serial f32 carry
  between tiles — the same chunked associative-scan structure as the ref
  oracle's ``associative_scan``, with the state never leaving VMEM.
* **decode** (S == 1) — grid ``(W/Wt,)``: one fused masked step batching
  *all* slots' single-token updates (decay, gate, ``sqrt(1-a²)``
  normalizer, output write in one kernel).  Inactive rows select their
  stored state bitwise via ``jnp.where`` — no cast, no recompute.

Gate linears stay in the model (they are already dispatched TT/int4
matmuls); production callers go through ``kernels.dispatch.rglru_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scan_tile(a, b):
    """Inclusive Hillis–Steele scan of ``h_t = a_t h_{t-1} + b_t`` (axis 0).

    Static log-depth: combine (a1,b1)⊕(a2,b2) = (a1·a2, a2·b1 + b2) with
    shifted operands (identity pad a=1, b=0).  Returns the prefix (A, B)
    arrays: ``h_t = A_t h_in + B_t``.
    """
    t = a.shape[0]
    d = 1
    while d < t:
        a_sh = jnp.concatenate(
            [jnp.ones((d,) + a.shape[1:], a.dtype), a[:-d]], axis=0)
        b_sh = jnp.concatenate(
            [jnp.zeros((d,) + b.shape[1:], b.dtype), b[:-d]], axis=0)
        a, b = a_sh * a, a * b_sh + b
        d *= 2
    return a, b


def _prefill_kernel(la_ref, gx_ref, h0_ref, pos_ref, h_ref, hlast_ref, *,
                    token_tile: int, n_tiles: int, out_dtype):
    la = la_ref[0].astype(jnp.float32)  # (S, Wt)
    gx = gx_ref[0].astype(jnp.float32)
    m = (pos_ref[0] >= 0).astype(jnp.float32)[:, None]  # (S, 1)
    la = la * m  # pads: log a = 0 -> a = 1
    a = jnp.exp(la)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * la), 1e-12)) * gx * m

    def body(t, h):
        a_t = jax.lax.dynamic_slice_in_dim(a, t * token_tile, token_tile)
        b_t = jax.lax.dynamic_slice_in_dim(b, t * token_tile, token_tile)
        pa, pb = _scan_tile(a_t, b_t)
        h_tile = pa * h[None, :] + pb
        h_ref[0, pl.ds(t * token_tile, token_tile)] = h_tile.astype(out_dtype)
        return h_tile[-1]

    h_last = jax.lax.fori_loop(0, n_tiles, body, h0_ref[0].astype(jnp.float32))
    hlast_ref[0] = h_last


def _decode_kernel(la_ref, gx_ref, h0_ref, pos_ref, h_ref, hlast_ref, *,
                   out_dtype):
    la = la_ref[:, 0].astype(jnp.float32)  # (B, Wt)
    gx = gx_ref[:, 0].astype(jnp.float32)
    h0 = h0_ref[...].astype(jnp.float32)
    active = (pos_ref[:, 0] >= 0)[:, None]
    a = jnp.exp(la)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * la), 1e-12)) * gx
    h = jnp.where(active, a * h0 + b, h0)  # inactive rows: bitwise h0
    h_ref[:, 0] = h.astype(out_dtype)
    hlast_ref[...] = h


def rglru_scan_pallas(log_a, gx, h0, pos=None, *, scan_dtype=None,
                      token_tile: int = 16, width_tile: int = 128,
                      interpret: bool):
    """Fused RG-LRU scan.  Same contract as ``kernels.ref.rglru_scan``:
    log_a/gx (B,S,W), h0 (B,W) f32, pos (B,S) int32 (``-1`` = padding) or
    None (all steps real).  Returns (h (B,S,W) scan_dtype, h_last (B,W) f32).
    """
    b, s, w = log_a.shape
    out_dtype = jnp.dtype(scan_dtype or jnp.float32)
    f32 = jnp.float32
    log_a, gx, h0 = log_a.astype(f32), gx.astype(f32), h0.astype(f32)
    pos = (jnp.zeros((b, s), jnp.int32) if pos is None
           else pos.astype(jnp.int32))

    wt = min(width_tile, w)
    pad_w = (-w) % wt
    if pad_w:  # zero-pad width: a = 1, b = 0, h0 = 0 -> pad lanes stay 0
        pad3 = ((0, 0), (0, 0), (0, pad_w))
        log_a, gx = jnp.pad(log_a, pad3), jnp.pad(gx, pad3)
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    nwt = (w + pad_w) // wt

    if s == 1:
        h, h_last = pl.pallas_call(
            functools.partial(_decode_kernel, out_dtype=out_dtype),
            grid=(nwt,),
            in_specs=[
                pl.BlockSpec((b, 1, wt), lambda j: (0, 0, j)),
                pl.BlockSpec((b, 1, wt), lambda j: (0, 0, j)),
                pl.BlockSpec((b, wt), lambda j: (0, j)),
                pl.BlockSpec((b, 1), lambda j: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((b, 1, wt), lambda j: (0, 0, j)),
                pl.BlockSpec((b, wt), lambda j: (0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(log_a.shape, out_dtype),
                jax.ShapeDtypeStruct(h0.shape, f32),
            ],
            name="rglru_scan",
            interpret=interpret,
        )(log_a, gx, h0, pos)
        return h[:, :, :w] if pad_w else h, h_last[:, :w] if pad_w else h_last

    tt = min(token_tile, s)
    pad_s = (-s) % tt
    if pad_s:  # pad steps ride at position -1: exact state passthrough
        ext = ((0, 0), (0, pad_s), (0, 0))
        log_a, gx = jnp.pad(log_a, ext), jnp.pad(gx, ext)
        pos = jnp.pad(pos, ((0, 0), (0, pad_s)), constant_values=-1)
    sp = s + pad_s

    h, h_last = pl.pallas_call(
        functools.partial(_prefill_kernel, token_tile=tt, n_tiles=sp // tt,
                          out_dtype=out_dtype),
        grid=(b, nwt),
        in_specs=[
            pl.BlockSpec((1, sp, wt), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, sp, wt), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, wt), lambda i, j: (i, j)),
            pl.BlockSpec((1, sp), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, sp, wt), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, wt), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, w + pad_w), out_dtype),
            jax.ShapeDtypeStruct((b, w + pad_w), f32),
        ],
        name="rglru_scan",
        interpret=interpret,
    )(log_a, gx, h0, pos)
    return h[:, :s, :w], h_last[:, :w]
