"""Typed InferenceSession / StateBackend serving API (DESIGN.md §7).

Every model family serves through the same two-piece contract:

* A **state backend** — a pytree of decode state plus the pure step
  functions over it.  Three concrete layouts:

  - ``paged``     — shared K/V block pools + per-slot block tables
                    (attention families, full attention).
  - ``ring``      — per-slot K/V rings of ``window + chunk`` entries
                    (sliding-window attention; also valid for full
                    attention at ``max_len`` ring width).
  - ``recurrent`` — constant-size recurrent state (griffin: RG-LRU h/conv
                    + windowed attention rings; rwkv: wkv/token-shift).
  - ``encdec``    — paged decoder self-attention + per-slot encoder
                    cross-attention context (whisper).

* An :class:`InferenceSession` handle exposing the uniform surface the
  engine consumes::

      init_state()                                     -> state pytree
      prefill_chunk(params, state, tokens, positions[, slots])
                                                       -> (logits (B,C,V), state)
      decode_step(params, state, tokens, positions)    -> (logits (B,V),  state)

  ``tokens``/``positions`` follow one convention everywhere: rows are decode
  slots, positions are per-sequence absolute token indices, and ``-1`` marks
  padding/inactive rows, so a single fixed-shape program covers every
  schedule state (ragged batches, mixed prefill progress, idle slots).  A
  backend whose only per-slot state is its block table also takes
  ``slots``, one slot id per prefill row: the tile then holds only the rows
  being prefilled.

Capabilities are **declared**, not probed: :data:`FAMILY_BACKENDS` is the
family × backend matrix, and :func:`make_session` raises a
``NotImplementedError`` naming the family when an unsupported backend is
requested (replacing the old ``hasattr(mod, "init_paged_cache")`` sniffing).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from . import griffin, rwkv, transformer, whisper

CACHE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "float16": jnp.float16, "int8": jnp.int8}


def canonical_cache_dtype(dtype) -> str:
    """Normalize a user-facing cache dtype (str or jnp dtype) to its name."""
    if isinstance(dtype, str):
        if dtype not in CACHE_DTYPES:
            raise ValueError(f"unknown cache dtype {dtype!r}")
        return dtype
    name = jnp.dtype(dtype).name
    if name not in CACHE_DTYPES:
        raise ValueError(f"unknown cache dtype {dtype!r}")
    return name


@dataclass(frozen=True)
class SessionSpec:
    """Static geometry of one serving session.

    ``slots`` is the decode-batch width.  An admitted request prefills *in
    its slot*: as its row of a (slots, chunk) tile with idle rows riding
    along at position ``-1``, or, where the block table is the only
    per-slot state, as a row of a tile of the admitted requests alone.
    ``num_blocks`` defaults to full occupancy plus the reserved null block
    for block-pool backends.
    """
    slots: int
    max_len: int
    prefill_chunk: int = 32
    block_size: int = 16
    num_blocks: int | None = None
    cache_dtype: str = "float32"

    def resolved_num_blocks(self) -> int:
        from ..serve.kv_cache import blocks_for
        if self.num_blocks is not None:
            return self.num_blocks
        return 1 + self.slots * blocks_for(self.max_len, self.block_size)

    def table_width(self) -> int:
        from ..serve.kv_cache import blocks_for
        return blocks_for(self.max_len, self.block_size)


class InferenceSession:
    """Base session: cfg + spec + the uniform step surface.

    Device-side methods (``init_state`` / ``prefill_chunk`` / ``decode_step``
    / ``begin_sequence``) are pure functions of their arguments given the
    static ``cfg`` — ``serve.steps.session_step_fns`` jits them once per
    (session type, cfg, kernel backend) and reuses the trace across engines.
    Host-side capacity accounting (block tables) lives in the engine, which
    owns a ``BlockManager`` whenever :attr:`uses_blocks` is set.
    """
    backend = "?"
    #: block-pool capacity accounting applies (paged KV memory)
    uses_blocks = False
    #: requests carry encoder context written at admission (enc-dec)
    needs_encoder_ctx = False

    def __init__(self, cfg: ModelConfig, spec: SessionSpec):
        self.cfg = cfg
        self.spec = spec

    @property
    def step_key(self):
        return (type(self), self.cfg)

    def _dtype(self):
        return CACHE_DTYPES[canonical_cache_dtype(self.spec.cache_dtype)]

    # -- device-side ----------------------------------------------------------
    def init_state(self):
        raise NotImplementedError

    def prefill_chunk(self, params, state, tokens, positions):
        """tokens (B,C), positions (B,C) -> (logits (B,C,V) f32, state).

        Rows are the decode slots in order.  A backend whose only per-slot
        state is its block table takes a fifth argument ``slots`` (B,) int32,
        the slot each row prefills for (default: all slots, in order)."""
        raise NotImplementedError

    def decode_step(self, params, state, tokens, positions):
        """tokens (B,1), positions (B,) -> (logits (B,V) f32, state)."""
        raise NotImplementedError

    def begin_sequence(self, params, state, slot, enc_frames):
        """Write per-request context (enc-dec only) into ``state`` at ``slot``."""
        raise NotImplementedError(
            f"family {self.cfg.family!r} has no per-request context")

    # -- host-side ------------------------------------------------------------
    def with_tables(self, state, block_tables):
        """Swap the host-packed block tables into the state pytree."""
        return state


class PagedKVSession(InferenceSession):
    """Shared K/V block pools + block tables (dense/moe, full attention)."""
    backend = "paged"
    uses_blocks = True

    def init_state(self):
        sp = self.spec
        return {
            "kv": transformer.init_paged_cache(
                self.cfg, sp.resolved_num_blocks(), sp.block_size, self._dtype()),
            "block_tables": jnp.zeros((sp.slots, sp.table_width()), jnp.int32),
        }

    def prefill_chunk(self, params, state, tokens, positions, slots=None):
        tables = state["block_tables"]
        if slots is not None:  # a tile of the admitted rows: their tables
            tables = tables[slots]
        logits, kv = transformer.prefill_paged_chunk(
            params, self.cfg, state["kv"], tokens, tables, positions)
        return logits, dict(state, kv=kv)

    def decode_step(self, params, state, tokens, positions):
        logits, kv = transformer.decode_step_paged(
            params, self.cfg, state["kv"], tokens, state["block_tables"], positions)
        return logits, dict(state, kv=kv)

    def with_tables(self, state, block_tables):
        return dict(state, block_tables=jnp.asarray(block_tables, jnp.int32))


class RingKVSession(InferenceSession):
    """Per-slot K/V rings (dense/moe; the sliding-window backend)."""
    backend = "ring"

    def init_state(self):
        sp = self.spec
        return {"kv": transformer.init_ring_cache(
            self.cfg, sp.slots, sp.max_len, sp.prefill_chunk, self._dtype())}

    def prefill_chunk(self, params, state, tokens, positions):
        logits, kv = transformer.prefill_ring_chunk(
            params, self.cfg, state["kv"], tokens, positions)
        return logits, {"kv": kv}

    def decode_step(self, params, state, tokens, positions):
        logits, kv = transformer.decode_step_ring(
            params, self.cfg, state["kv"], tokens, positions)
        return logits, {"kv": kv}


class GriffinSession(InferenceSession):
    """Constant-size recurrent state: RG-LRU h + conv tails + windowed
    attention rings (griffin / recurrentgemma)."""
    backend = "recurrent"

    def init_state(self):
        sp = self.spec
        return griffin.init_session_state(self.cfg, sp.slots, sp.max_len,
                                          sp.prefill_chunk, self._dtype())

    def prefill_chunk(self, params, state, tokens, positions):
        return griffin.prefill_session_chunk(params, self.cfg, state, tokens,
                                             positions)

    def decode_step(self, params, state, tokens, positions):
        return griffin.decode_session_step(params, self.cfg, state, tokens,
                                           positions)


class RwkvSession(InferenceSession):
    """Constant-size recurrent state: wkv matrices + token-shift tails."""
    backend = "recurrent"

    def init_state(self):
        return rwkv.init_session_state(self.cfg, self.spec.slots, self._dtype())

    def prefill_chunk(self, params, state, tokens, positions):
        return rwkv.prefill_session_chunk(params, self.cfg, state, tokens,
                                          positions)

    def decode_step(self, params, state, tokens, positions):
        return rwkv.decode_session_step(params, self.cfg, state, tokens,
                                        positions)


class EncDecSession(InferenceSession):
    """Paged decoder self-attention + per-slot encoder context (whisper)."""
    backend = "encdec"
    uses_blocks = True
    needs_encoder_ctx = True

    def init_state(self):
        sp = self.spec
        state = whisper.init_session_state(
            self.cfg, sp.slots, sp.resolved_num_blocks(), sp.block_size,
            self._dtype())
        state["block_tables"] = jnp.zeros((sp.slots, sp.table_width()), jnp.int32)
        return state

    def prefill_chunk(self, params, state, tokens, positions):
        logits, new = whisper.prefill_session_chunk(
            params, self.cfg, {"self": state["self"], "cross": state["cross"]},
            tokens, state["block_tables"], positions)
        return logits, dict(new, block_tables=state["block_tables"])

    def decode_step(self, params, state, tokens, positions):
        logits, new = whisper.decode_session_step(
            params, self.cfg, {"self": state["self"], "cross": state["cross"]},
            tokens, state["block_tables"], positions)
        return logits, dict(new, block_tables=state["block_tables"])

    def begin_sequence(self, params, state, slot, enc_frames):
        ctx = whisper.encode_ctx(params, self.cfg, enc_frames)  # (L,1,T,H,Dh)
        cross = {
            "k": state["cross"]["k"].at[:, slot].set(ctx["k"][:, 0]),
            "v": state["cross"]["v"].at[:, slot].set(ctx["v"][:, 0]),
        }
        return dict(state, cross=cross)

    def with_tables(self, state, block_tables):
        return dict(state, block_tables=jnp.asarray(block_tables, jnp.int32))


# ---------------------------------------------------------------------------
# Capability matrix (explicit — replaces hasattr probing) + constructor
# ---------------------------------------------------------------------------
FAMILY_BACKENDS: dict[str, tuple[str, ...]] = {
    "dense": ("paged", "ring"),
    "moe": ("paged", "ring"),
    "griffin": ("recurrent",),
    "rwkv": ("recurrent",),
    "encdec": ("encdec",),
}

#: backends whose state carries per-slot scale tables, making
#: ``cache_dtype='int8'`` lossless up to the payload's own rounding: paged
#: pools and per-slot rings quantize each written K/V entry, the recurrent
#: backends quantize the wkv/conv state through the scan kernels'
#: fused scale-table load/store (the RG-LRU carry ``h`` stays f32).
INT8_SCALED_BACKENDS = ("paged", "ring", "recurrent", "encdec")

_SESSION_TYPES: dict[tuple[str, str], type[InferenceSession]] = {
    ("dense", "paged"): PagedKVSession,
    ("moe", "paged"): PagedKVSession,
    ("dense", "ring"): RingKVSession,
    ("moe", "ring"): RingKVSession,
    ("griffin", "recurrent"): GriffinSession,
    ("rwkv", "recurrent"): RwkvSession,
    ("encdec", "encdec"): EncDecSession,
}


def default_backend(cfg: ModelConfig) -> str:
    """The family's preferred backend: block pools for full attention,
    rings for sliding windows, recurrent/encdec state otherwise."""
    if cfg.family in ("dense", "moe"):
        return "ring" if cfg.window else "paged"
    if cfg.family in ("griffin", "rwkv"):
        return "recurrent"
    if cfg.family == "encdec":
        return "encdec"
    raise ValueError(f"unknown family {cfg.family!r}")


def make_session(cfg_or_model, spec: SessionSpec | None = None, *,
                 backend: str | None = None, **spec_kw) -> InferenceSession:
    """Build the typed session for a config (or Model).

    ``backend=None`` picks :func:`default_backend`.  Unsupported
    combinations raise ``NotImplementedError`` naming the family, so an
    engine asking for the wrong layout fails loudly at construction instead
    of deep inside a jitted step.
    """
    cfg: ModelConfig = getattr(cfg_or_model, "cfg", cfg_or_model)
    if spec is None:
        spec = SessionSpec(**spec_kw)
    allowed = FAMILY_BACKENDS.get(cfg.family)
    if allowed is None:
        raise ValueError(f"unknown family {cfg.family!r}")
    backend = backend or default_backend(cfg)
    if backend not in allowed:
        raise NotImplementedError(
            f"family {cfg.family!r} ({cfg.name}) has no {backend!r} state "
            f"backend; available: {', '.join(allowed)}")
    if backend == "paged" and cfg.window:
        raise NotImplementedError(
            f"family {cfg.family!r} ({cfg.name}) uses sliding-window "
            f"attention (window={cfg.window}); the paged backend assumes "
            "full attention — use the 'ring' backend")
    if backend in ("paged", "ring") and cfg.pos_type not in ("rope", "none"):
        raise NotImplementedError(
            f"family {cfg.family!r} ({cfg.name}) has pos_type "
            f"{cfg.pos_type!r}; the {backend!r} backend supports rope|none")
    if canonical_cache_dtype(spec.cache_dtype) == "int8" \
            and backend not in INT8_SCALED_BACKENDS:
        raise NotImplementedError(
            f"cache_dtype 'int8' needs per-slot scale tables; the "
            f"{backend!r} backend stores its state unscaled (a raw int8 "
            "cast would corrupt outputs) — use a float cache dtype")
    return _SESSION_TYPES[cfg.family, backend](cfg, spec)
