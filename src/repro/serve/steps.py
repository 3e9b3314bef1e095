"""Serving-side step builders, sharding rules, and config transforms.

Jitted program construction for the engine lives here: one
backend-parameterized builder, :func:`session_step_fns`, jits a session's
uniform ``prefill_chunk`` / ``decode_step`` surface (plus the enc-dec
``begin_sequence`` context writer when the backend declares it).  Programs
are memoized per (session type, model config, kernel backend) so every
:class:`~repro.serve.engine.Engine` over the same model shares one trace
cache (the scheduler fuzz suite builds dozens of engines).  The
``chunked_prefill`` driver feeds several waiting prompts through repeated
fixed-width chunk calls of that one program — on a tile of only the
admitted rows where the session's sole per-slot state is its block table.

Sharding rules (the paper's deployment path): TTD stays on, all non-TT
linears go INT4 (w4a16), params are TP-sharded over ``model`` only (no FSDP
— decode latency wants weights resident).  KV caches shard batch over
``data`` and kv-heads / state width over ``model``.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig, QuantConfig
from ..kernels.dispatch import backend_override
from ..models.sessions import (  # noqa: F401  (re-exported for callers)
    CACHE_DTYPES,
    InferenceSession,
    canonical_cache_dtype,
)


def serve_config_of(cfg: ModelConfig, kernel_backend: str | None = None) -> ModelConfig:
    """Training config -> serving config (int4 weights for non-TT linears).

    ``kernel_backend`` pins the linear dispatch backend for the serve path
    (default: keep the config's policy — "auto" picks Pallas on TPU); see
    ``repro.kernels.dispatch``.
    """
    cfg = cfg.replace(quant=QuantConfig(enabled=True, bits=4, group_size=128),
                      param_dtype="bfloat16")
    if kernel_backend is not None:
        cfg = cfg.replace(kernel_backend=kernel_backend)
    return cfg


# ---------------------------------------------------------------------------
# Jitted step builders (shared across engine instances).  One path for every
# backend: the session's uniform surface is what gets jitted — there is no
# ring-vs-paged fork here anymore.
# ---------------------------------------------------------------------------
_STEP_CACHE: dict = {}


def session_step_fns(session: InferenceSession, kernel_backend: str | None = None):
    """(prefill_chunk, decode, begin) jitted programs for one session type.

    Memoized on (session type, model config, kernel backend): the device
    step methods are pure given the static config, so engines over the same
    model share one trace cache regardless of their SessionSpec — geometry
    differences only change argument shapes, which jit re-specializes on
    naturally.  Compression rides the config, not the params: two engines
    serving the same architecture under different compression specs
    (TT ranks, int4 groups, TT embed) carry different ``ModelConfig``s and
    therefore get distinct cache entries — TT-core / int4 / embed-core
    leaves are ordinary traced arguments inside each program
    (tests/test_compressed_serve.py pins this).  ``begin`` is ``None``
    unless the backend declares ``needs_encoder_ctx``.  The kernel backend
    resolves at trace time, so the engine's choice (if any) is pinned into
    all programs.
    """
    key = (*session.step_key, kernel_backend)
    if key not in _STEP_CACHE:
        while len(_STEP_CACHE) >= 64:  # bounded like the old lru_cache
            _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
        def _prefill(params, state, tokens, positions, slots=None,
                     _s=session, _kb=kernel_backend):
            slot_arg = () if slots is None else (slots,)
            with backend_override(_kb):
                return _s.prefill_chunk(params, state, tokens, positions,
                                        *slot_arg)

        def _decode(params, state, tokens, positions, _s=session,
                    _kb=kernel_backend):
            with backend_override(_kb):
                return _s.decode_step(params, state, tokens, positions)

        begin = None
        if session.needs_encoder_ctx:
            def begin(params, state, slot, enc_frames, _s=session,
                      _kb=kernel_backend):
                with backend_override(_kb):
                    return _s.begin_sequence(params, state, slot, enc_frames)
            begin = jax.jit(begin)
        _STEP_CACHE[key] = (jax.jit(_prefill), jax.jit(_decode), begin)
    return _STEP_CACHE[key]


@jax.jit
def _greedy_tokens(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]


def greedy_tokens(logits):
    """Device-side greedy sampling for the dispatch-ahead path.

    (slots, V) logits -> (slots, 1) int32 token column, bitwise the per-row
    ``argmax`` the synchronous engine samples on host — the async front-end
    feeds it straight into the next tick's dispatch and pulls it to host
    while that tick computes (DESIGN.md §12).
    """
    return _greedy_tokens(logits)


_NULL_CTX = contextlib.nullcontext()


def chunked_prefill(prefill_chunk_fn, params, state, prompts, *, chunk: int,
                    slots=None, span=None):
    """Prefill several prompts through repeated fixed-width chunk calls.

    prompts: one token list per tile row; ``None``/empty rows are padding
    riding along at position ``-1`` (their writes are dropped / routed to the
    null block by every backend).  Without ``slots`` the tile is the whole
    decode batch: row *i* is slot *i*.  With ``slots`` (one slot id per row)
    the tile holds only the admitted rows, and each call passes the ids on
    as the program's fifth positional argument, so the program reads that
    slot's state (its block-table row).  Every call processes a
    (rows, chunk) tile, so the admitted prompts prefill together in
    ``ceil(longest/chunk)`` jitted calls of one static shape.  Returns
    (last_logits (rows, V) f32 — zeros for padding rows — and the updated
    state).

    ``span(chunk_index, n_chunks)``, when given, returns a context manager
    entered around each chunk call (the engine's obs layer times
    ``serve/prefill_chunk`` and emits ``prefill_chunk`` events through it;
    ``None`` — the default — costs nothing).
    """
    b = len(prompts)
    lens = [len(p) if p else 0 for p in prompts]
    max_l = max(max(lens), 1)
    n_chunks = -(-max_l // chunk)
    toks = np.zeros((b, n_chunks * chunk), np.int32)
    pos = np.full((b, n_chunks * chunk), -1, np.int32)
    for i, p in enumerate(prompts):
        if p:
            toks[i, :len(p)] = p
            pos[i, :len(p)] = np.arange(len(p))
    slot_arg = () if slots is None else (jnp.asarray(slots, jnp.int32),)
    last = [None] * b
    for c in range(n_chunks):
        sl = slice(c * chunk, (c + 1) * chunk)
        with (span(c, n_chunks) if span is not None else _NULL_CTX):
            logits, state = prefill_chunk_fn(params, state,
                                             jnp.asarray(toks[:, sl]),
                                             jnp.asarray(pos[:, sl]), *slot_arg)
        for i, n in enumerate(lens):
            if n and c * chunk <= n - 1 < (c + 1) * chunk:
                last[i] = logits[i, (n - 1) % chunk]
    # padding rows (including the all-empty batch, whose single chunk ran at
    # position -1 with every write dropped) get a zero-logits row
    zero = jnp.zeros(logits.shape[-1], logits.dtype)
    return jnp.stack([x if x is not None else zero for x in last]), state


_PARAM_LEAF_NAMES = ("w", "table", "cores", "qweight", "scales", "b")


def _cache_leaf_rule(path, shape, mesh: Mesh, batch_axes):
    names = []
    for p in path:
        names.append(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))))
    leaf = names[-1]
    if leaf in _PARAM_LEAF_NAMES or (names and names[-2:-1] == ["cores"]):
        # the cache walk only knows *state* leaves; a compressed param tree
        # (TT cores / int4 qweight+scales / embed table) fed here would get
        # silently replicated — route params through dist.sharding instead
        raise ValueError(
            f"cache sharding rule got param leaf {'/'.join(names)!r}; "
            "session *state* only — shard params via "
            "repro.dist.sharding.param_shardings")
    nd = len(shape)
    intent = [None] * nd
    if leaf in ("k", "v"):
        # (..., B, W, Hkv, Dh); GQA often has Hkv < |model| — fall back to
        # sharding the head_dim so big caches still spread over TP
        if nd >= 4:
            intent[-4] = batch_axes
            n_model = mesh.shape.get("model", 1)
            if shape[-2] % n_model == 0:
                intent[-2] = "model"
            elif shape[-1] % n_model == 0:
                intent[-1] = "model"
    elif leaf in ("k_scale", "v_scale"):  # (..., B, W, Hkv) — rides its pool/ring
        if nd >= 3:
            intent[-3] = batch_axes
    elif leaf == "wkv":  # (..., B, H, dk, dv)
        if nd >= 4:
            intent[-4] = batch_axes
            intent[-3] = "model"
    elif leaf == "wkv_scale":  # (..., B, H)
        if nd >= 2:
            intent[-2] = batch_axes
            intent[-1] = "model"
    elif leaf == "h":  # (..., B, W)
        intent[-2] = batch_axes
        intent[-1] = "model"
    elif leaf == "conv":  # (..., B, cw-1, W)
        if nd >= 3:
            intent[-3] = batch_axes
            intent[-1] = "model"
    elif leaf == "conv_scale":  # (..., B, cw-1)
        if nd >= 2:
            intent[-2] = batch_axes
    elif leaf in ("x_tm", "x_cm"):  # (..., B, 1, D)
        if nd >= 3:
            intent[-3] = batch_axes
    # sanitize
    out = []
    for dim, e in enumerate(intent):
        if e is None:
            out.append(None)
            continue
        axes = e if isinstance(e, tuple) else (e,)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if not axes or shape[dim] % total != 0:
            out.append(None)
        else:
            out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def cache_pspecs(cache_shapes, mesh: Mesh):
    baxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    baxes = baxes if len(baxes) > 1 else baxes[0]
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _cache_leaf_rule(path, tuple(leaf.shape), mesh, baxes),
        cache_shapes)


def cache_shardings(cache_shapes, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_pspecs(cache_shapes, mesh),
                        is_leaf=lambda x: isinstance(x, P))
