"""Fused RWKV6 wkv recurrent-scan Pallas kernel.

The wkv recurrence keeps a per-(slot, head) matrix state
``S ∈ R^{hd×hd}``:

    S_t = diag(w_t) S_{t-1} + k_tᵀ v_t;   y_t = r_t (S_{t-1} + diag(u) k_tᵀ v_t)

Grid: ``(slot, head)`` — one program per state matrix, which stays resident
on-chip for the whole call while the chunk axis streams through it:

* **prefill** (S > 1) — the program walks ``S/C`` chunks of the
  chunked-parallel (Finch/GLA) form: per chunk, two (C×hd)·(hd×·) matmuls
  for the intra-chunk scores/output plus a rank-C state update — the same
  math as ``kernels.ref.wkv_chunked``, generalized from a host-side
  ``lax.scan`` into an in-kernel loop over the chunk grid axis.  Ragged
  tails are padded to a chunk multiple with identity steps (k = 0, w = 1),
  so a one-chunk prompt takes the matmul form too.
* **decode** (S == 1) — one fused masked step: decay, bonus ``u``, state
  update and output in one kernel, batching all slots via the grid.  The
  step uses ``w`` directly (no log-decay flooring), matching the sequential
  oracle exactly.

Masking follows the serving convention (``pos`` ``-1`` = padding → k = 0,
w = 1: the f32 state passes through bitwise).  int8 state rides per-(slot,
head) f32 scale tables fused into the kernel's load/store: dequantize at
entry, amax/127 requantize at exit, with fully-idle rows bitwise-preserving
their stored int8 values *and* scale.  Production callers go through
``kernels.dispatch.wkv_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import WKV_CHUNK, WKV_LOG_DECAY_FLOOR


def _kernel(r_ref, k_ref, v_ref, w_ref, pos_ref, u_ref, s0_ref, *refs,
            chunk: int, n_chunks: int, quantized: bool, decode: bool):
    if quantized:
        scale_ref, y_ref, sout_ref, scout_ref = refs
    else:
        scale_ref, (y_ref, sout_ref) = None, refs
    f32 = jnp.float32
    pos = pos_ref[0]  # (S,)
    m = (pos >= 0)[:, None]
    r = r_ref[0, :, 0].astype(f32)  # (S, hd)
    k = jnp.where(m, k_ref[0, :, 0].astype(f32), 0.0)
    w = jnp.where(m, w_ref[0, :, 0].astype(f32), 1.0)
    v = v_ref[0, :, 0].astype(f32)
    u = u_ref[0].astype(f32)  # (hd,)
    s0 = s0_ref[0, 0].astype(f32)  # (hd, hd)
    if quantized:
        s0 = s0 * scale_ref[0, 0]

    if decode:  # exact one-step update (no log-decay flooring)
        kv = k[0][:, None] * v[0][None, :]
        y = jnp.dot(r[0], s0 + u[:, None] * kv, preferred_element_type=f32)
        y_ref[0, 0, 0] = y
        s_fin = w[0][:, None] * s0 + kv
    else:
        lw = jnp.clip(jnp.log(jnp.maximum(w, 1e-38)), WKV_LOG_DECAY_FLOOR, 0.0)
        tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

        def body(c, s_c):
            rc = jax.lax.dynamic_slice_in_dim(r, c * chunk, chunk)
            kc = jax.lax.dynamic_slice_in_dim(k, c * chunk, chunk)
            vc = jax.lax.dynamic_slice_in_dim(v, c * chunk, chunk)
            lwc = jax.lax.dynamic_slice_in_dim(lw, c * chunk, chunk)
            la_inc = jnp.cumsum(lwc, axis=0)  # includes step τ's decay
            la_exc = la_inc - lwc             # decay before step t
            la_end = la_inc[-1]
            r_tld = rc * jnp.exp(la_exc)
            k_tld = kc * jnp.exp(-la_inc)
            k_end = kc * jnp.exp(la_end[None] - la_inc)
            scores = jnp.dot(r_tld, k_tld.T, preferred_element_type=f32)
            scores = jnp.where(tri, scores, 0.0)
            diag = jnp.sum(rc * u[None] * kc, axis=-1)  # (C,)
            y = jnp.dot(scores, vc, preferred_element_type=f32) \
                + diag[:, None] * vc \
                + jnp.dot(r_tld, s_c, preferred_element_type=f32)
            y_ref[0, pl.ds(c * chunk, chunk), 0] = y
            return s_c * jnp.exp(la_end)[:, None] \
                + jnp.dot(k_end.T, vc, preferred_element_type=f32)

        s_fin = jax.lax.fori_loop(0, n_chunks, body, s0)

    if quantized:
        idle = jnp.all(pos < 0)  # this slot saw no real step this call
        sc = jnp.maximum(jnp.max(jnp.abs(s_fin)), 1e-8) / 127.0
        q = jnp.round(s_fin / sc).astype(jnp.int8)
        sout_ref[0, 0] = jnp.where(idle, s0_ref[0, 0], q)
        scout_ref[0, 0] = jnp.where(idle, scale_ref[0, 0], sc)
    else:
        sout_ref[0, 0] = s_fin


def wkv_scan_pallas(r, k, v, w, u, state0, pos=None, *, state_scale=None,
                    chunk: int = WKV_CHUNK, interpret: bool):
    """Fused wkv scan.  Same contract as ``kernels.ref.wkv_scan``:
    r/k/v/w (B,S,H,hd), u (H,hd), state0 (B,H,hd,hd) f32 — or int8 with
    ``state_scale`` (B,H) f32 — pos (B,S) int32 (``-1`` = padding) or None.
    Returns (y (B,S,H,hd) f32, new_state, new_scale-or-None).
    """
    b, s, h, hd = r.shape
    f32 = jnp.float32
    quantized = state_scale is not None
    decode = s == 1
    pos = (jnp.zeros((b, s), jnp.int32) if pos is None
           else pos.astype(jnp.int32))

    c = 1 if decode else min(chunk, max(s, 2))
    pad_s = (-s) % c
    if pad_s:  # identity steps: k = 0, w = 1 (and pos = -1 for the mask)
        ext = ((0, 0), (0, pad_s), (0, 0), (0, 0))
        r, k, v = (jnp.pad(t, ext) for t in (r, k, v))
        w = jnp.pad(w, ext, constant_values=1.0)
        pos = jnp.pad(pos, ((0, 0), (0, pad_s)), constant_values=-1)
    sp = s + pad_s

    in_specs = [
        pl.BlockSpec((1, sp, 1, hd), lambda i, j: (i, 0, j, 0)),  # r
        pl.BlockSpec((1, sp, 1, hd), lambda i, j: (i, 0, j, 0)),  # k
        pl.BlockSpec((1, sp, 1, hd), lambda i, j: (i, 0, j, 0)),  # v
        pl.BlockSpec((1, sp, 1, hd), lambda i, j: (i, 0, j, 0)),  # w
        pl.BlockSpec((1, sp), lambda i, j: (i, 0)),               # pos
        pl.BlockSpec((1, hd), lambda i, j: (j, 0)),               # u
        pl.BlockSpec((1, 1, hd, hd), lambda i, j: (i, j, 0, 0)),  # state0
    ]
    args = [r, k, v, w.astype(f32), pos, u.astype(f32), state0]
    out_specs = [
        pl.BlockSpec((1, sp, 1, hd), lambda i, j: (i, 0, j, 0)),
        pl.BlockSpec((1, 1, hd, hd), lambda i, j: (i, j, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, sp, h, hd), f32),
        jax.ShapeDtypeStruct((b, h, hd, hd), state0.dtype),
    ]
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (i, j)))
        args.append(state_scale.astype(f32))
        out_specs.append(pl.BlockSpec((1, 1), lambda i, j: (i, j)))
        out_shape.append(jax.ShapeDtypeStruct((b, h), f32))

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=c, n_chunks=sp // c,
                          quantized=quantized, decode=decode),
        grid=(b, h),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="wkv_scan",
        interpret=interpret,
    )(*args)
    if quantized:
        y, st, sc = out
        return y[:, :s], st, sc
    y, st = out
    return y[:, :s], st, None
