"""Backend-dispatching linear execution layer.

Every linear in the model zoo — dense, Tensor-Train (paper §II), int4 w4a16
(paper §IV) — routes through this module, which picks an execution backend
and carries the fused epilogue operands (scale, bias, residual, activation —
the paper's TTDLinear-BN(-Res) operator fusion, §III.A) all the way into the
kernel instead of applying them as separate HBM round-trips.

Backends
--------
``ref``              pure-JAX staged contraction / dequant matmul (CPU, and
                     the oracle every kernel is tested against)
``pallas-interpret`` the Pallas kernels executed by the Pallas interpreter
                     (CPU validation of the exact kernel body)
``pallas``           the Pallas kernels lowered via Mosaic (real TPU)
``auto``             ``pallas`` when ``jax.default_backend() == "tpu"``,
                     else ``ref``

Resolution order (first non-empty wins; ``auto`` then resolves per device):

    explicit call arg > ``backend_override()`` context > per-role env
    (``REPRO_KERNEL_BACKEND_<ROLE>``) > ``REPRO_KERNEL_BACKEND`` env >
    ``ModelConfig.kernel_backend`` (carried on ``LinearSpec.backend``) > auto

Resolution happens at trace time (backends are static), so a jitted step
bakes in whatever policy was active when it was first traced.

The dense kind has no Pallas kernel on purpose: XLA's native matmul already
saturates the MXU, and the epilogue below fuses into it; the backend argument
is accepted for uniformity and ignored.
"""
from __future__ import annotations

import contextlib
import contextvars
import os
import re

import jax
import jax.numpy as jnp

from ..core.ttd import TTSpec
from ..obs import MetricsRegistry
from . import ref
from .epilogue import apply_epilogue
from .int4_matmul import int4_matmul_pallas
from .paged_attention import paged_attention_pallas
from .prefill_attention import prefill_attention_pallas
from .scan_rglru import rglru_scan_pallas
from .scan_wkv import wkv_scan_pallas
from .tt_embed import tt_embed_pallas
from .tt_linear import tt_linear_pallas

BACKENDS = ("ref", "pallas-interpret", "pallas")
ENV_VAR = "REPRO_KERNEL_BACKEND"

_override: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "repro_kernel_backend_override", default=None)


def _check(backend: str) -> str:
    if backend not in BACKENDS + ("auto",):
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    return backend


@contextlib.contextmanager
def backend_override(backend: str | None):
    """Force a backend for everything traced inside the context."""
    if backend is None:
        yield
        return
    token = _override.set(_check(backend))
    try:
        yield
    finally:
        _override.reset(token)


def _role_env(role: str) -> str | None:
    if not role:
        return None
    return os.environ.get(f"{ENV_VAR}_{re.sub(r'[^A-Za-z0-9]', '_', role).upper()}")


def resolve_backend(explicit: str | None = None, *, role: str = "",
                    preferred: str = "") -> str:
    """Resolve the policy chain to a concrete backend name."""
    for cand in (explicit, _override.get(), _role_env(role),
                 os.environ.get(ENV_VAR), preferred or None):
        if cand:
            cand = _check(cand)
            if cand != "auto":
                return cand
            break  # an explicit "auto" stops the chain and resolves by device
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# Dispatch observability (DESIGN.md §9).  ``resolve_backend`` runs at trace
# time, so a "dispatch" here means one trace-time resolution (or one eager
# call) — NOT one executed device launch of a cached jitted program.  That is
# exactly what the consumers need: ``resolved_backend(role)`` answers "which
# backend did the program that actually traced in this process bake in?",
# replacing benchmark self-reports of the *requested* backend.  Counters live
# in a module-local zero-dep registry so recording costs a dict lookup + float
# add and never touches the device.  Kernel time comes from a profiler trace,
# where every Pallas kernel carries its stable ``name=``.
# ---------------------------------------------------------------------------
_METRICS = MetricsRegistry()
_LAST_RESOLVED: dict[str, str] = {}


def kernel_metrics() -> MetricsRegistry:
    """Registry holding the ``kernel_dispatch_total{role,backend}`` counters."""
    return _METRICS


def resolved_backend(role: str) -> str | None:
    """Backend most recently resolved for ``role`` in this process (what a
    traced program actually baked in), or ``None`` if never dispatched."""
    return _LAST_RESOLVED.get(role)


def dispatch_counts() -> dict[tuple[str, str], int]:
    """{(role, resolved backend): trace-time dispatch count}."""
    return {(lab["role"], lab["backend"]): int(m.value)
            for name, lab, m in _METRICS.collect()
            if name == "kernel_dispatch_total"}


def reset_dispatch_metrics() -> None:
    _METRICS.reset()
    _LAST_RESOLVED.clear()


def _record_dispatch(role: str, backend: str, out):
    """Count the (role, backend) dispatch."""
    _LAST_RESOLVED[role] = backend
    _METRICS.counter("kernel_dispatch_total", role=role, backend=backend).inc()
    return out


# ---------------------------------------------------------------------------
# Dispatched ops.  All accept (..., N) inputs (leading dims flattened for the
# kernel grids) and the full epilogue operand set; all return x.dtype.
# ---------------------------------------------------------------------------
def dense_linear(x, w, *, scale=None, bias=None, residual=None,
                 activation: str | None = None, backend: str | None = None,
                 role: str = ""):
    """y = act(x W [* scale] [+ b]) [+ residual];  (…, N) @ (N, M).

    Epilogue runs on the f32 accumulator (XLA fuses it into the matmul);
    ``backend`` is ignored — see module docstring (the dispatch counter
    records the honest ``xla`` label).
    """
    del backend
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = apply_epilogue(y, scale=scale, bias=bias, residual=residual,
                       activation=activation)
    return _record_dispatch(role or "dense", "xla", y.astype(x.dtype))


def tt_linear(x, cores, spec: TTSpec, *, scale=None, bias=None, residual=None,
              activation: str | None = None, backend: str | None = None,
              block_b: int | None = None, role: str = ""):
    """(…, N) -> (…, M) through the staged TT contraction + fused epilogue."""
    backend = resolve_backend(backend, role=role)
    if backend == "ref":
        # keep leading dims intact: activation sharding (batch→data,
        # seq→model) propagates untouched through the stages (DESIGN.md §4)
        y = ref.tt_linear_bn_res(x, cores, spec, scale=scale, bias=bias,
                                 residual=residual, activation=activation)
    else:
        lead = x.shape[:-1]
        xf = x.reshape(-1, spec.n_in)
        rf = residual.reshape(-1, spec.n_out) if residual is not None else None
        y = tt_linear_pallas(xf, cores, spec, scale=scale, bias=bias,
                             residual=rf, activation=activation,
                             block_b=block_b,
                             interpret=(backend == "pallas-interpret"))
        y = y.reshape(*lead, spec.n_out)
    return _record_dispatch(role or "tt", backend, y)


def tt_embed(ids, cores, spec: TTSpec, *, backend: str | None = None,
             role: str = "embed_lookup"):
    """Row gather of a TT-compressed embedding table (TensorGPT layout).

    ids: int32 of any shape (padding ids resolve like the dense
    ``jnp.take`` path: negative wrap once, then clamp into range);
    returns (…, D) f32 rows of the (V, D) table
    the cores describe — ``spec`` has M = V, N = D.  ``ref`` runs the
    digit-indexed chain in ``kernels/ref.py``; the Pallas backends the
    one-hot-gather tile kernel (``kernels/tt_embed.py``).
    """
    backend = resolve_backend(backend, role=role)
    if backend == "ref":
        y = ref.tt_embedding(ids, cores, spec)
    else:
        lead = ids.shape
        flat = jnp.asarray(ids, jnp.int32).reshape(-1)
        y = tt_embed_pallas(flat, cores, spec,
                            interpret=(backend == "pallas-interpret"))
        y = y.reshape(*lead, spec.n_in)
    return _record_dispatch(role, backend, y)


def paged_attention(q, cache, block_tables, qpos, *, sm_scale=None,
                    backend: str | None = None, role: str = "attn_paged"):
    """Decode attention through a paged KV cache's block table.

    q: (B, H, Dh) — one query token per sequence; qpos: (B,) absolute
    positions (-1 = inactive row → zeros).  ``ref`` gathers the context and
    runs the masked-softmax oracle; the Pallas backends run the fused
    online-softmax kernel (``kernels/paged_attention.py``).  Chunked prefill
    (Sq > 1) goes through :func:`prefill_attention` instead.
    """
    backend = resolve_backend(backend, role=role)
    if backend == "ref":
        y = ref.paged_attention(q[:, None], cache, block_tables,
                                qpos[:, None], sm_scale=sm_scale)[:, 0]
    else:
        y = paged_attention_pallas(q, cache, block_tables, qpos,
                                   sm_scale=sm_scale,
                                   interpret=(backend == "pallas-interpret"))
    return _record_dispatch(role, backend, y)


def prefill_attention(q, qpos, *, cache=None, block_tables=None, k=None,
                      v=None, kpos=None, window: int = 0, sm_scale=None,
                      k_scale=None, v_scale=None,
                      backend: str | None = None, role: str = "attn_prefill"):
    """Ragged chunked-prefill attention over a paged pool or per-slot rings.

    q: (B, Sq, H, Dh); qpos: (B, Sq) absolute positions (``-1`` = padding
    row → zeros).  Pass either ``cache`` + ``block_tables`` (paged layout)
    or ``k``/``v`` + ``kpos`` (ring layout — ``kpos`` ``-1`` = empty entry).
    ``ref`` runs the gather/masked-softmax oracles in ``kernels/ref.py``;
    the Pallas backends run the fused streaming kernel
    (``kernels/prefill_attention.py``) — same policy chain as
    ``paged_attention``, resolved at trace time.

    Ring layout optionally carries int8 ``k``/``v`` with per-entry-per-head
    f32 ``k_scale``/``v_scale`` (B, Wr, Hkv) tables; dequantization is fused
    into the kernel's tile loads.
    """
    backend = resolve_backend(backend, role=role)
    paged = cache is not None or block_tables is not None
    ring = k is not None or v is not None or kpos is not None
    if paged == ring:
        raise ValueError("prefill_attention takes exactly one layout: "
                         "cache+block_tables (paged) or k/v/kpos (ring)")
    if paged and (cache is None or block_tables is None):
        raise ValueError("paged layout needs both cache and block_tables")
    if ring and (k is None or v is None or kpos is None):
        raise ValueError("ring layout needs all of k, v and kpos")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if k_scale is not None and not ring:
        raise ValueError("k_scale/v_scale are ring-layout only")
    if paged:
        if backend == "ref":
            y = ref.paged_attention(q, cache, block_tables, qpos,
                                    sm_scale=sm_scale, window=window)
        else:
            y = prefill_attention_pallas(
                q, qpos, cache=cache, block_tables=block_tables, window=window,
                sm_scale=sm_scale, interpret=(backend == "pallas-interpret"))
    elif backend == "ref":
        y = ref.ring_attention(q, k, v, qpos, kpos, window=window,
                               sm_scale=sm_scale, k_scale=k_scale,
                               v_scale=v_scale)
    else:
        y = prefill_attention_pallas(
            q, qpos, k=k, v=v, kpos=kpos, window=window, sm_scale=sm_scale,
            k_scale=k_scale, v_scale=v_scale,
            interpret=(backend == "pallas-interpret"))
    return _record_dispatch(role, backend, y)


def rglru_scan(log_a, gx, h0, pos=None, *, scan_dtype=None,
               backend: str | None = None, role: str = "rglru_scan"):
    """Fused RG-LRU recurrence ``h_t = a h_{t-1} + sqrt(1-a²)(i ⊙ u)``.

    log_a/gx: (B, S, W) pre-gate log-decay and gated input; h0: (B, W) f32
    carried state; pos: (B, S) absolute positions (``-1`` = padding step →
    exact state passthrough; a fully ``-1`` row keeps ``h0`` bitwise).
    Returns ``(h (B, S, W) scan_dtype, h_last (B, W) f32)``.  ``ref`` runs
    the ``associative_scan`` oracle; the Pallas backends keep the state
    resident on-chip (``kernels/scan_rglru.py``) — S == 1 takes the fused
    masked decode-step kernel batching all slots.
    """
    if log_a.shape != gx.shape or log_a.ndim != 3:
        raise ValueError(f"log_a/gx must both be (B, S, W); got "
                         f"{log_a.shape} vs {gx.shape}")
    if h0.shape != (log_a.shape[0], log_a.shape[2]):
        raise ValueError(f"h0 must be (B, W) = {(log_a.shape[0], log_a.shape[2])}; "
                         f"got {h0.shape}")
    backend = resolve_backend(backend, role=role)
    if backend == "ref":
        out = ref.rglru_scan(log_a, gx, h0, pos, scan_dtype=scan_dtype)
    else:
        out = rglru_scan_pallas(log_a, gx, h0, pos, scan_dtype=scan_dtype,
                                interpret=(backend == "pallas-interpret"))
    return _record_dispatch(role, backend, out)


def wkv_scan(r, k, v, w, u, state0, pos=None, *, state_scale=None,
             backend: str | None = None, role: str = "wkv_scan"):
    """Fused RWKV6 wkv recurrence over per-(slot, head) matrix state.

    r/k/v/w: (B, S, H, hd); u: (H, hd); state0: (B, H, hd, hd) f32 — or int8
    with per-(slot, head) f32 ``state_scale`` (B, H) fused into the kernel's
    state load/store; pos: (B, S) absolute positions (``-1`` = padding →
    identity step; a fully ``-1`` row keeps state *and* scale bitwise).
    Returns ``(y (B, S, H, hd) f32, new_state, new_scale-or-None)``.  S > 1
    takes the chunked-parallel matmul form (short prompts are padded to a
    chunk multiple, so a single chunk qualifies too); S == 1 the fused
    masked decode step.
    """
    if r.shape != k.shape or r.shape != v.shape or r.shape != w.shape \
            or r.ndim != 4:
        raise ValueError("r/k/v/w must share one (B, S, H, hd) shape; got "
                         f"{r.shape}/{k.shape}/{v.shape}/{w.shape}")
    if state0.shape != (r.shape[0], r.shape[2], r.shape[3], r.shape[3]):
        raise ValueError(f"state0 must be (B, H, hd, hd); got {state0.shape}")
    if (state_scale is None) != (state0.dtype != jnp.int8):
        raise ValueError("int8 state0 requires state_scale (and vice versa); "
                         f"got state0 {state0.dtype} with state_scale "
                         f"{'set' if state_scale is not None else 'None'}")
    backend = resolve_backend(backend, role=role)
    if backend == "ref":
        out = ref.wkv_scan(r, k, v, w, u, state0, pos,
                           state_scale=state_scale)
    else:
        out = wkv_scan_pallas(r, k, v, w, u, state0, pos,
                              state_scale=state_scale,
                              interpret=(backend == "pallas-interpret"))
    return _record_dispatch(role, backend, out)


def int4_matmul(x, qweight, scales, *, group: int = 128, scale=None, bias=None,
                residual=None, activation: str | None = None,
                backend: str | None = None, role: str = ""):
    """(…, K) -> (…, M) through the w4a16 kernel + fused epilogue."""
    backend = resolve_backend(backend, role=role)
    if backend == "ref":
        y = ref.int4_matmul(x, qweight, scales, group=group, scale=scale,
                            bias=bias, residual=residual,
                            activation=activation)
    else:
        lead = x.shape[:-1]
        xf = x.reshape(-1, x.shape[-1])
        rf = (residual.reshape(-1, qweight.shape[0])
              if residual is not None else None)
        y = int4_matmul_pallas(xf, qweight, scales, group=group, scale=scale,
                               bias=bias, residual=rf, activation=activation,
                               interpret=(backend == "pallas-interpret"))
        y = y.reshape(*lead, qweight.shape[0])
    return _record_dispatch(role or "int4", backend, y)
