"""Continuous-batching serving engine over the typed session API.

One scheduler serves every model family (DESIGN.md §7): a Python loop
drives the jitted programs built by ``serve.steps.session_step_fns`` from an
:class:`~repro.models.sessions.InferenceSession` — the family-specific state
layout (paged K/V blocks, per-slot rings, recurrent state, encoder context)
is entirely the backend's business.  The scheduler sees one uniform surface:

* ``prefill_chunk(params, state, tokens, positions[, slots])`` — admitted
  prompts prefill *batched* in fixed-width chunks.  Where the block table is
  the only per-slot state (paged sessions) the tile is ``min(prefill_batch,
  slots)`` rows of admitted prompts, each naming its slot; otherwise its
  rows are the decode slots and idle slots ride along at position ``-1``.
* ``decode_step(params, state, tokens, positions)`` — one call per tick
  regardless of position raggedness (per-sequence positions).

Requests join after prefill; every decode tick advances all active slots one
token; finished sequences free their resources immediately — classic
continuous batching.  For block-pool backends (``session.uses_blocks``) the
engine owns a :class:`~repro.serve.kv_cache.BlockManager`: admission is
FCFS while free blocks cover the prompt plus one lookahead token, tables
grow on demand each tick, and block exhaustion preempts the newest-admitted
sequence back to the waiting queue (recompute-style: its blocks are freed;
emitted tokens are kept and re-prefilled with the prompt on re-admission, so
greedy outputs are unchanged).  Constant-state backends never preempt —
their capacity is the slot itself.

Requests can be **cancelled** mid-flight (``Engine.cancel`` — queued or
active; an active occupant releases its slot and blocks through the same
machinery as a preemption, keeping the tokens already emitted) and carry an
optional **deadline** (``submit(deadline_s=)``; ``tick`` cancels expired
requests with a ``deadline_miss`` trace event before admitting).  Admission
order is a pluggable :class:`AdmissionPolicy` (FCFS default, EDF available);
the decode tick itself is decomposed into schedule → dispatch → collect so
the asyncio front-end (``serve.frontend``, DESIGN.md §12) can overlap host
scheduling with device compute via dispatch-ahead double buffering.

First-token latency (``Request.t_first``) is stamped only after
``jax.block_until_ready`` on the prefill logits — timing the dispatch
instead of the computation understates TTFT by the entire prefill on an
async backend.  All timing fields are ``time.perf_counter()`` stamps
(monotonic — a wall-clock step can never corrupt a latency); the only
wall-clock value kept is the informational ``Request.t_submit_wall``.

Observability (DESIGN.md §9): pass ``obs=`` an
:class:`~repro.obs.Observer` / :class:`~repro.obs.ObsConfig` (or set
``REPRO_OBS=1``) and the engine emits structured scheduler events
(admit / prefill_chunk / decode_tick / preempt / finish / pool_sample),
queue-time / TTFT / inter-token latency histograms, block-pool
utilization gauges and the prefill tile's rows against its rows that hold
tokens (``serve_prefill_tile_rows_total`` / ``serve_prefill_real_rows_total``),
and times each stage in a span: ``serve/admit`` (with
``serve/prefill_chunk`` (``tile_rows``, ``real_rows``), ``serve/prefill_wait``
and ``serve/first_sample`` inside), ``serve/decode_schedule``, ``serve/decode_dispatch`` (``ahead``,
``starved``), ``serve/decode_collect``, and the overlay
``serve/host_bound``: from a sync that left no device work in flight while
requests are live until the next dispatch has enqueued its device work.
Disabled (the default), the hot
path pays one ``is None`` check per site — no events, no allocation, no
device syncs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..models.sessions import (
    InferenceSession,
    SessionSpec,
    canonical_cache_dtype,
    make_session,
)
from ..obs import resolve_observer
from . import steps
from .kv_cache import BlockManager, blocks_for, pack_block_tables

_NULL_CTX = contextlib.nullcontext()  # reusable no-op span (obs disabled)


def _leaf_name(path) -> str:
    """Key of a state leaf within its parent (``"block_tables"``, ``"k"``)."""
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_tokens: int
    eos: int | None = None
    enc_frames: Any = None  # (T_enc, D) encoder frames (enc-dec families)
    deadline_s: float | None = None  # completion budget from submit (seconds)
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    finish_reason: str = ""  # eos | max_tokens | max_len | user | deadline
    # monotonic (perf_counter) stamps — duration math only ever uses these
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    # informational wall-clock submit time (never used in arithmetic)
    t_submit_wall: float = 0.0


@dataclass
class TickPlan:
    """One decode tick's host-side schedule, frozen at dispatch time.

    ``active``/``rids`` pin which request occupied each scheduled slot when
    the tick launched — collection skips a slot whose occupant changed while
    the tick was in flight (a cancellation between dispatch and collect).
    ``toks`` is the host token batch, or ``None`` when the dispatcher is
    handed a device-resident token array instead (the dispatch-ahead path:
    the previous tick's on-device argmax feeds the next tick without a
    host round-trip).
    """

    active: list[int]            # scheduled slot ids
    rids: list[int]              # per-active-slot request id (staleness check)
    positions: np.ndarray        # (slots,) int32; -1 = idle row
    toks: np.ndarray | None      # (slots, 1) int32 host tokens, or None


class AdmissionPolicy:
    """Orders the waiting queue for admission (the policy seam, DESIGN §12).

    ``order`` returns the waiting requests in admission-priority order; the
    engine walks that order and stops at the first request that does not fit
    (head-of-line semantics *within the policy's order*, so a policy
    reorders priorities but cannot starve the pool-capacity invariants).
    """

    name = "policy"

    def order(self, queue: list[Request], now: float) -> list[Request]:
        raise NotImplementedError


class FCFSAdmission(AdmissionPolicy):
    """First-come-first-served: the queue order is the admission order."""

    name = "fcfs"

    def order(self, queue: list[Request], now: float) -> list[Request]:
        return queue


class EDFAdmission(AdmissionPolicy):
    """Earliest-deadline-first: requests with the nearest absolute deadline
    admit first; deadline-free requests follow in FCFS order."""

    name = "edf"

    def order(self, queue: list[Request], now: float) -> list[Request]:
        return sorted(queue, key=lambda r: (
            (0, r.t_submit + r.deadline_s) if r.deadline_s is not None
            else (1, r.t_submit)))


class Engine:
    """Backend-parameterized continuous-batching scheduler.

    ``model`` may be a :class:`~repro.models.api.Model`, a ``ModelConfig``,
    or a prebuilt :class:`~repro.models.sessions.InferenceSession`.
    ``backend=None`` picks the family default (paged for full-attention
    dense/moe, rings for SWA, recurrent state for griffin/rwkv, encoder
    context + paged self-attention for whisper); asking for an unsupported
    backend raises ``NotImplementedError`` naming the family.
    """

    def __init__(self, model, params, *, slots: int | None = None,
                 max_len: int | None = None, backend: str | None = None,
                 block_size: int | None = None, num_blocks: int | None = None,
                 cache_dtype=None, prefill_batch: int = 2,
                 prefill_chunk: int | None = None, greedy: bool = True,
                 temperature: float = 1.0, top_k: int = 0, seed: int = 0,
                 kernel_backend: str | None = None, obs=None,
                 admission: AdmissionPolicy | None = None):
        geometry = dict(slots=slots, max_len=max_len, block_size=block_size,
                        num_blocks=num_blocks, cache_dtype=cache_dtype,
                        prefill_chunk=prefill_chunk, backend=backend)
        if isinstance(model, InferenceSession):
            passed = [k for k, v in geometry.items() if v is not None]
            if passed:
                raise ValueError(
                    "a prebuilt InferenceSession fixes the serving geometry; "
                    f"drop the conflicting kwargs {passed} or pass the "
                    "config/Model instead")
            self.session = model
        else:
            cfg = getattr(model, "cfg", model)
            self.session = make_session(cfg, SessionSpec(
                slots=slots if slots is not None else 4,
                max_len=max_len if max_len is not None else 512,
                prefill_chunk=max(1, prefill_chunk if prefill_chunk is not None else 32),
                block_size=block_size if block_size is not None else 16,
                num_blocks=num_blocks,
                cache_dtype=canonical_cache_dtype(
                    cache_dtype if cache_dtype is not None else "float32")),
                backend=backend)
        self.cfg: ModelConfig = self.session.cfg
        spec = self.session.spec
        self.params = params
        self.slots = spec.slots
        self.max_len = spec.max_len
        self.prefill_batch = max(1, prefill_batch)
        self.prefill_chunk = spec.prefill_chunk
        self.greedy = greedy
        self.temperature = temperature
        self.top_k = top_k
        self._key = jax.random.PRNGKey(seed)
        self.kernel_backend = kernel_backend  # None -> dispatch policy chain

        self.manager: BlockManager | None = None
        if self.session.uses_blocks:
            self.manager = BlockManager(spec.resolved_num_blocks(),
                                        spec.block_size)
        self.state = self.session.init_state()
        self._batch_axis = self._find_batch_axes()
        # where the block table is the only per-slot state, a prefill tile
        # holds just the admitted rows (each names its slot); otherwise it
        # spans every slot (None), since compacting would need a gather and
        # a scatter of the per-slot state rows
        compact = all(axis < 0 or _leaf_name(path) == "block_tables"
                      for path, axis in
                      jax.tree_util.tree_leaves_with_path(self._batch_axis))
        self._prefill_rows = (min(self.prefill_batch, self.slots) if compact
                              else None)
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.admission = admission if admission is not None else FCFSAdmission()
        self._any_deadline = False  # cheap guard for the per-tick expiry scan
        self._next_rid = 0
        self.slot_req: list[Request | None] = [None] * self.slots
        self.slot_pos = np.zeros(self.slots, np.int32)  # next position to decode
        self._admit_order: list[int] = []  # slots, oldest admission first
        self._prefill, self._decode, self._begin = steps.session_step_fns(
            self.session, kernel_backend)

        # -- observability (obs=None -> env default; False -> force off) ------
        self.obs = resolve_observer(obs)
        self._tick_no = 0
        self._t_last_tok: dict[int, float] = {}  # slot -> last token stamp
        self._host_bound = None   # open serve/host_bound overlay span
        self._last_logits = None  # the latest decode dispatch's result
        if self.obs is not None:
            reg = self.obs.registry
            self._h_queue = reg.histogram("serve_queue_seconds")
            self._h_ttft = reg.histogram("serve_ttft_seconds")
            self._h_intertok = reg.histogram("serve_inter_token_seconds")
            self._c_tokens = reg.counter("serve_tokens_total")
            self._c_ticks = reg.counter("serve_decode_ticks_total")
            self._c_preempt = reg.counter("serve_preemptions_total")
            self._c_cancel = reg.counter("serve_cancellations_total")
            self._c_deadline = reg.counter("serve_deadline_miss_total")
            self._c_ahead = reg.counter("serve_ahead_ticks_total")
            self._c_tile_rows = reg.counter("serve_prefill_tile_rows_total")
            self._c_real_rows = reg.counter("serve_prefill_real_rows_total")
            self._g_active = reg.gauge("serve_active_slots")
            if self.manager is not None:
                self._g_util = reg.gauge("serve_pool_utilization")
                self._g_free = reg.gauge("serve_pool_free_blocks")
                self._g_live = reg.gauge("serve_pool_live_tokens")

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: list[int], max_tokens: int = 32,
               eos: int | None = None, enc_frames=None,
               deadline_s: float | None = None) -> Request:
        if not prompt:
            raise ValueError("empty prompt")
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be a positive completion budget in seconds "
                f"(got {deadline_s!r} with max_tokens={max_tokens}); omit it "
                "for no deadline")
        if len(prompt) + 1 > self.max_len:
            raise ValueError(f"prompt needs {len(prompt) + 1} positions "
                             f"> max_len {self.max_len}")
        if self.manager is not None:
            # a request must be servable *alone* (worst case: everything
            # else preempted): its total footprint — prompt + generated,
            # capped by the max_len frontier — must fit the whole pool
            worst = min(len(prompt) + max_tokens, self.max_len)
            need = blocks_for(worst, self.manager.block_size)
            if need > self.manager.num_blocks - 1:
                raise ValueError(
                    f"request needs up to {need} blocks but the pool only "
                    f"has {self.manager.num_blocks - 1}")
        req = Request(self._next_rid, list(prompt), max_tokens, eos,
                      enc_frames=enc_frames, deadline_s=deadline_s,
                      t_submit=time.perf_counter(),
                      # analyze: allow[wall-clock] informational submit stamp; never enters duration math
                      t_submit_wall=time.time())
        self._next_rid += 1
        self._any_deadline |= deadline_s is not None
        self.queue.append(req)
        if self.obs is not None:
            self.obs.event("submit", t=req.t_submit, rid=req.rid,
                           prompt_len=len(req.prompt), max_tokens=max_tokens)
        return req

    def pending(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def tick(self) -> None:
        """One scheduler step: expire deadlines, admit waiting requests
        (batched chunked prefill), then decode one token for every active
        sequence.  Decoding is schedule → dispatch → collect so an async
        front-end can interleave host work between dispatch and collect
        (dispatch-ahead double buffering, DESIGN.md §12)."""
        self._expire_deadlines()
        self._admit()
        plan = self._decode_schedule()
        if plan is not None:
            logits = self._decode_dispatch(plan)
            self._decode_collect(plan, logits)
        self._finish_tick()

    def _finish_tick(self) -> None:
        """Per-tick epilogue shared by ``tick`` and the async pump."""
        if self.obs is not None:
            self._sample_pool()
        self._tick_no += 1

    def cancel(self, req: Request, reason: str = "user") -> bool:
        """Cancel a queued or mid-flight request, freeing its slot/blocks.

        Emitted tokens are kept on the request; an active occupant goes
        through the same slot/block release as a preemption, so the freed
        capacity admits the next waiting request on the following tick.
        Returns ``False`` when the request already finished (cancellation
        raced completion) — callers treat that as a no-op."""
        if req.done:
            return False
        slot = -1
        if not self._remove_from_queue(req):
            for s, r in enumerate(self.slot_req):
                if r is req:
                    slot = s
                    self.slot_req[s] = None
                    self._admit_order.remove(s)
                    self._t_last_tok.pop(s, None)
                    if self.manager is not None:
                        self.manager.free(req.rid)
                    break
            else:
                return False  # not queued, not active: nothing to cancel
        req.cancelled = True
        req.done = True
        req.finish_reason = reason
        req.t_done = time.perf_counter()
        self.finished.append(req)
        if self.obs is not None:
            self._c_cancel.inc()
            self.obs.event("cancel", t=req.t_done, rid=req.rid, slot=slot,
                           tick=self._tick_no, reason=reason)
        return True

    def _remove_from_queue(self, req: Request) -> bool:
        # identity-based: dataclass __eq__ would compare enc_frames arrays
        for i, r in enumerate(self.queue):
            if r is req:
                del self.queue[i]
                return True
        return False

    def _expired_requests(self, now: float) -> list[Request]:
        live = self.queue + [r for r in self.slot_req if r is not None]
        return [r for r in live if r.deadline_s is not None
                and now - r.t_submit > r.deadline_s]

    def _deadline_due(self) -> bool:
        """True when some live request's deadline has already passed (the
        async pump breaks its dispatch-ahead chain to expire it)."""
        return self._any_deadline and \
            bool(self._expired_requests(time.perf_counter()))

    def _expire_deadlines(self) -> int:
        """Cancel every live request whose completion deadline has passed."""
        if not self._any_deadline:
            return 0
        now = time.perf_counter()
        expired = self._expired_requests(now)
        for req in expired:
            if self.obs is not None:
                self._c_deadline.inc()
                self.obs.event("deadline_miss", t=now, rid=req.rid,
                               tick=self._tick_no, deadline_s=req.deadline_s)
            self.cancel(req, reason="deadline")
        return len(expired)

    def _sample_pool(self) -> None:
        """Record pool-utilization gauges + a pool_sample event (obs on)."""
        active = sum(r is not None for r in self.slot_req)
        self._g_active.set(active)
        if self.manager is None:
            return
        if self._tick_no % self.obs.config.pool_sample_every:
            return
        util = self.manager.utilization()
        free = self.manager.num_free
        live = self.manager.live_tokens()
        self._g_util.set(util)
        self._g_free.set(free)
        self._g_live.set(live)
        self.obs.event("pool_sample", tick=self._tick_no, utilization=util,
                       free_blocks=free, live_tokens=live, active_slots=active)

    def run(self, max_ticks: int = 10_000) -> list[Request]:
        """Tick until drained; returns the requests finished by *this* call.

        The engine stays usable after draining: a later ``submit`` + ``run``
        serves normally, and the return value never replays earlier runs'
        requests (``self.finished`` keeps the cumulative history)."""
        start = len(self.finished)
        ticks = 0
        while self.pending() and ticks < max_ticks:
            self.tick()
            ticks += 1
        if self.obs is not None:
            self._host_bound_close()
        return self.finished[start:]

    @property
    def num_free_blocks(self) -> int | None:
        return self.manager.num_free if self.manager is not None else None

    # -- shared internals -----------------------------------------------------
    def _sample(self, logits) -> int:
        """Greedy argmax, or seeded temperature/top-k sampling."""
        if self.greedy:
            # analyze: allow[host-sync] legacy per-token path; the batched tick samples on-device
            return int(jnp.argmax(logits))
        self._key, sub = jax.random.split(self._key)
        scaled = logits.astype(jnp.float32) / max(self.temperature, 1e-6)
        if self.top_k > 0:
            k = min(self.top_k, scaled.shape[-1])
            kth = jax.lax.top_k(scaled, k)[0][-1]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        # analyze: allow[host-sync] seeded sampling emits one host token by contract
        return int(jax.random.categorical(sub, scaled))

    def _emit(self, req: Request, tok: int) -> bool:
        """Record one sampled token; returns True when the request is done."""
        req.out_tokens.append(tok)
        if self.obs is not None:
            self._c_tokens.inc()
        if req.eos is not None and tok == req.eos:
            self._finish(req, "eos")
            return True
        if len(req.out_tokens) >= req.max_tokens:
            self._finish(req, "max_tokens")
            return True
        return False

    def _finish(self, req: Request, reason: str) -> None:
        req.done = True
        req.finish_reason = reason
        req.t_done = time.perf_counter()
        self.finished.append(req)
        if self.obs is not None:
            self.obs.event("finish", t=req.t_done, rid=req.rid,
                           tick=self._tick_no, reason=reason,
                           n_out=len(req.out_tokens))

    def _seq_tokens(self, req: Request) -> list[int]:
        """Tokens a (re-)admitted request must prefill: the prompt plus
        anything already emitted before a preemption."""
        return req.prompt + req.out_tokens

    def _find_batch_axes(self):
        """Identify each state leaf's slot axis structurally (dim sizes like
        n_layers can collide with the slot count)."""
        spec = self.session.spec
        # pin the block-pool size: the default scales with ``slots``, and a
        # pool dim that grows with the probe would masquerade as a slot axis
        bigger = type(self.session)(self.cfg, dataclasses.replace(
            spec, slots=spec.slots + 1, num_blocks=spec.resolved_num_blocks()))
        sa = jax.eval_shape(self.session.init_state)
        sb = jax.eval_shape(bigger.init_state)
        return jax.tree.map(
            lambda a, b: next((i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                               if x != y), -1), sa, sb)

    def _reset_slots(self, slot_ids: list[int]):
        """Clear per-slot state rows before a new occupant prefills (a stale
        ring/recurrent state would otherwise leak into the new sequence).
        Block-pool leaves have no slot axis and are skipped — block ownership
        already isolates sequences there."""
        mask = np.zeros(self.slots, bool)
        mask[slot_ids] = True
        m = jnp.asarray(mask)

        def upd(path, leaf, axis):
            if axis < 0 or _leaf_name(path) == "block_tables":
                return leaf
            mb = m.reshape((1,) * axis + (self.slots,) + (1,) * (leaf.ndim - axis - 1))
            fill = -1 if leaf.dtype == jnp.int32 else 0
            return jnp.where(mb, jnp.asarray(fill, leaf.dtype), leaf)

        self.state = jax.tree_util.tree_map_with_path(upd, self.state,
                                                      self._batch_axis)

    def _sync_tables(self, extra: dict[int, int] | None = None):
        """Re-pack per-slot block tables into the state (block backends)."""
        if self.manager is None:
            return
        rids: list[int | None] = [r.rid if r is not None else None
                                  for r in self.slot_req]
        for s, rid in (extra or {}).items():
            rids[s] = rid
        bt = pack_block_tables(self.manager, rids, self.session.spec.table_width())
        self.state = self.session.with_tables(self.state, bt)

    # -- admission ------------------------------------------------------------
    def _admit(self):
        """Policy-ordered admission (FCFS by default): take waiting requests
        while a slot is free and — for block backends — the pool covers their
        prompt plus one lookahead token, then prefill them together in
        fixed-width chunks."""
        free_slots = [s for s in range(self.slots) if self.slot_req[s] is None]
        if not (self.queue and free_slots):
            return
        span = self.obs.span("serve/admit") if self.obs is not None else _NULL_CTX
        with span:
            batch = self._take_batch(free_slots)
            if self.obs is not None:
                span.fields.update(
                    rids=[req.rid for _, req in batch],
                    n_tokens=sum(len(self._seq_tokens(r)) for _, r in batch))
            if batch:
                self._prefill_batch(batch)

    def _take_batch(self, free_slots: list[int]) -> list[tuple[int, Request]]:
        """(slot, request) pairs admitted now, in the policy's order."""
        batch: list[tuple[int, Request]] = []
        reserve = 0  # lookahead blocks promised to earlier batch members
        order = self.admission.order(list(self.queue), time.perf_counter())
        for req in order:
            if not free_slots or len(batch) >= self.prefill_batch:
                break
            n_tok = len(self._seq_tokens(req))
            if self.manager is not None:
                # admission wants the prompt *plus one lookahead token*
                # free — counting lookahead already reserved by this
                # batch's earlier members — so a fresh admission doesn't
                # immediately preempt on its first decode tick
                bs = self.manager.block_size
                need = blocks_for(n_tok + 1, bs)
                if need + reserve > self.manager.num_free or \
                        not self.manager.allocate(req.rid, n_tok):
                    break  # head-of-line blocks: keep the policy order
                reserve += need - blocks_for(n_tok, bs)
            self._remove_from_queue(req)
            batch.append((free_slots.pop(0), req))
        return batch

    def _prefill_batch(self, batch: list[tuple[int, Request]]) -> None:
        """Prefill ``batch`` in chunks, stamp first tokens and sample them."""
        seqs = [self._seq_tokens(req) for _, req in batch]
        if self._prefill_rows is None:  # the (slots, chunk) tile: row = slot
            rows = [s for s, _ in batch]
            prompts: list[list[int] | None] = [None] * self.slots
            for s, seq in zip(rows, seqs):
                prompts[s] = seq
            slot_ids = None
        else:  # the admitted rows only; padding rows name slot 0
            pad = self._prefill_rows - len(batch)
            rows = list(range(len(batch)))
            prompts = seqs + [None] * pad
            slot_ids = [s for s, _ in batch] + [0] * pad
        chunk_span = None
        if self.obs is not None:
            t_admit = time.perf_counter()
            for (s, req), seq in zip(batch, seqs):
                self.obs.event("admit", t=t_admit, rid=req.rid, slot=s,
                               tick=self._tick_no, n_tokens=len(seq))
                if not req.t_first:  # first admission, not a preempt replay
                    self._h_queue.observe(t_admit - req.t_submit)
            rids = [req.rid for _, req in batch]
            tile_rows = len(prompts)

            @contextlib.contextmanager
            def chunk_span(c, n_chunks):
                real_rows = sum(len(seq) > c * self.prefill_chunk
                                for seq in seqs)
                self._c_tile_rows.inc(tile_rows)
                self._c_real_rows.inc(real_rows)
                with self.obs.span("serve/prefill_chunk", chunk=c,
                                   n_chunks=n_chunks, tile_rows=tile_rows,
                                   real_rows=real_rows):
                    yield
                    if c == 0:  # the device has work: host-bound ends
                        self._host_bound_close()
                self.obs.event("prefill_chunk", tick=self._tick_no, chunk=c,
                               n_chunks=n_chunks, rids=rids,
                               tile_rows=tile_rows, real_rows=real_rows)
        self._reset_slots([s for s, _ in batch])
        if self.session.needs_encoder_ctx:
            for s, req in batch:
                frames = req.enc_frames
                if frames is None:
                    frames = np.zeros((self.cfg.enc_len, self.cfg.d_model),
                                      np.float32)
                self.state = self._begin(self.params, self.state, jnp.int32(s),
                                         jnp.asarray(frames)[None])
        self._sync_tables(extra={s: req.rid for s, req in batch})
        logits, self.state = steps.chunked_prefill(
            self._prefill, self.params, self.state, prompts,
            chunk=self.prefill_chunk, slots=slot_ids, span=chunk_span)
        with (self.obs.span("serve/prefill_wait") if self.obs is not None
              else _NULL_CTX):
            # first-token latency: stamp only after the device finishes
            jax.block_until_ready(logits)
        t_ready = time.perf_counter()
        if self.obs is not None:
            self._host_bound_open()
        with (self.obs.span("serve/first_sample") if self.obs is not None
              else _NULL_CTX):
            for (s, req), row, seq in zip(batch, rows, seqs):
                fresh = not req.t_first
                if fresh:
                    req.t_first = t_ready
                    if self.obs is not None:
                        self._h_ttft.observe(t_ready - req.t_submit)
                        self.obs.event("first_token", t=t_ready, rid=req.rid,
                                       tick=self._tick_no,
                                       ttft_s=t_ready - req.t_submit)
                self._t_last_tok[s] = t_ready
                tok = self._sample(logits[row])
                if self._emit(req, tok):  # eos on first token / max_tokens=1
                    self._t_last_tok.pop(s, None)
                    if self.manager is not None:
                        self.manager.free(req.rid)
                    continue
                self.slot_req[s] = req
                self.slot_pos[s] = len(seq)
                self._admit_order.append(s)
        if self.obs is not None and not self.pending():
            self._host_bound_close()

    # -- host-bound device idle (obs on) --------------------------------------
    def _host_bound_open(self) -> None:
        """A sync just returned with no device work in flight while requests
        are live: the device waits on the host until the next dispatch."""
        if self._host_bound is None:
            self._host_bound = self.obs.overlay("serve/host_bound")

    def _host_bound_close(self) -> None:
        """The next dispatch, once its device work is enqueued, (or the
        drain) ends the host-bound stretch."""
        if self._host_bound is not None:
            self._host_bound.close()
            self._host_bound = None

    # -- decode / preemption --------------------------------------------------
    def _preempt_newest(self) -> int | None:
        """Free the most recently admitted sequence back to the waiting
        queue's head; returns its slot.  Recompute-style: emitted tokens
        ride along and are re-prefilled with the prompt on re-admission."""
        for s in reversed(self._admit_order):
            if self.slot_req[s] is None:
                continue
            req = self.slot_req[s]
            self.manager.free(req.rid)
            self.slot_req[s] = None
            self._admit_order.remove(s)
            self.queue.insert(0, req)
            self._t_last_tok.pop(s, None)
            if self.obs is not None:
                self._c_preempt.inc()
                self.obs.event("preempt", rid=req.rid, slot=s,
                               tick=self._tick_no)
            return s
        return None

    def _decode_schedule(self) -> TickPlan | None:
        """Host-side tick planning: grow block tables (preempting on
        exhaustion), pick the active slots, and build the token/position
        batch.  Returns ``None`` when nothing is active."""
        with (self.obs.span("serve/decode_schedule", ahead=False)
              if self.obs is not None else _NULL_CTX):
            # block backends: grow each active sequence's table to cover the
            # incoming token, preempting the newest-admitted sequence on block
            # exhaustion (the grower itself, if it is the newest — FCFS favors
            # older requests)
            if self.manager is not None:
                for s in list(self._admit_order):
                    req = self.slot_req[s]
                    if req is None:
                        continue
                    while not self.manager.ensure(req.rid, int(self.slot_pos[s]) + 1):
                        victim = self._preempt_newest()
                        if victim == s:
                            break  # the grower was evicted; retries on re-admission
                        if victim is None:  # unreachable: submit-time capacity check
                            raise RuntimeError(
                                f"block pool too small: sequence {req.rid} alone "
                                f"cannot grow to {int(self.slot_pos[s]) + 1} tokens")
            active = [s for s in range(self.slots) if self.slot_req[s] is not None]
            if not active:
                return None
            if self.obs is not None:
                self._c_ticks.inc()
                self.obs.event("decode_tick", tick=self._tick_no,
                               active=len(active))
            toks = np.zeros((self.slots, 1), np.int32)
            positions = np.full((self.slots,), -1, np.int32)
            for s in active:
                toks[s, 0] = self.slot_req[s].out_tokens[-1]
                positions[s] = self.slot_pos[s]
            return TickPlan(active=active,
                            rids=[self.slot_req[s].rid for s in active],
                            positions=positions, toks=toks)

    def _plan_ahead(self, plan: TickPlan) -> TickPlan | None:
        """Plan the tick *after* an in-flight ``plan`` without its token
        values (dispatch-ahead, DESIGN.md §12).

        Safe only when every in-flight slot is guaranteed to survive its
        emission — greedy sampling (tokens can come from a device-side
        argmax), no eos watch, not at the max_tokens/max_len frontier — and
        the pool can grow one more token per sequence without preempting.
        Returns ``None`` otherwise; the caller falls back to collecting the
        in-flight tick first."""
        if not self.greedy:
            return None  # host-side RNG sampling needs the logits on host
        with (self.obs.span("serve/decode_schedule", ahead=True)
              if self.obs is not None else _NULL_CTX):
            for i, s in enumerate(plan.active):
                req = self.slot_req[s]
                if req is None or req.rid != plan.rids[i] or req.eos is not None:
                    return None
                # after the in-flight emission the request must still be
                # live: not its last max_tokens emission, not at the max_len
                # frontier
                if len(req.out_tokens) + 1 >= req.max_tokens:
                    return None
                if int(plan.positions[s]) + 1 >= self.max_len - 1:
                    return None
            if self.manager is not None:
                for s in plan.active:
                    # position p+1 writes token p+1 -> needs p+2 covered;
                    # bail to the synchronous path rather than preempt
                    # around an uncollected tick
                    if not self.manager.ensure(self.slot_req[s].rid,
                                               int(plan.positions[s]) + 2):
                        return None
            positions = np.full((self.slots,), -1, np.int32)
            for s in plan.active:
                positions[s] = plan.positions[s] + 1
            if self.obs is not None:
                self._c_ticks.inc()
                # the in-flight tick has not collected yet, so _tick_no
                # still names it; the ahead tick is the next one
                self.obs.event("decode_tick", tick=self._tick_no + 1,
                               active=len(plan.active))
            return TickPlan(active=list(plan.active), rids=list(plan.rids),
                            positions=positions, toks=None)

    def _decode_dispatch(self, plan: TickPlan, device_toks=None):
        """Launch the jitted decode step for ``plan`` (async under jax);
        ``device_toks`` (a (slots, 1) int32 device array) substitutes for the
        host token batch on the dispatch-ahead path.

        With obs on, an ahead dispatch whose predecessor (the tick still in
        flight) has already finished is *starved*: the device waited on the
        host.  ``is_ready`` does not block."""
        span = _NULL_CTX
        if self.obs is not None:
            ahead = device_toks is not None
            starved = ahead and self._last_logits is not None and \
                self._last_logits.is_ready()
            if ahead:
                self._c_ahead.inc()
            span = self.obs.span(
                "serve/decode_dispatch",
                tick=self._tick_no + 1 if ahead else self._tick_no,
                active=len(plan.active), ahead=ahead, starved=starved)
        with span:
            self._sync_tables()
            toks = (device_toks if device_toks is not None
                    else jnp.asarray(plan.toks))
            logits, self.state = self._decode(self.params, self.state, toks,
                                              jnp.asarray(plan.positions))
            if self.obs is not None:
                self._host_bound_close()  # the tick is enqueued
        if self.obs is not None:
            self._last_logits = logits
        return logits

    def _decode_collect(self, plan: TickPlan, logits, toks_host=None):
        """Sample/record one token per scheduled slot and run the finish
        bookkeeping.  ``toks_host`` (a (slots,) int sequence) skips sampling
        — the dispatch-ahead path already pulled the device argmax.  Slots
        whose occupant changed since dispatch (cancelled mid-flight) are
        skipped; their computed token is discarded.

        On the synchronous path (no ``toks_host``) the first sample waits
        for the tick, after which no device work is in flight: with obs on,
        ``serve/host_bound`` opens there."""
        with (self.obs.span("serve/decode_collect", tick=self._tick_no)
              if self.obs is not None else _NULL_CTX):
            for i, s in enumerate(plan.active):
                req = self.slot_req[s]
                if req is None or req.rid != plan.rids[i]:
                    continue  # cancelled while the tick was in flight
                if toks_host is not None:
                    tok = int(toks_host[s])
                else:
                    tok = self._sample(logits[s])
                    if self.obs is not None:
                        self._host_bound_open()
                self.slot_pos[s] += 1
                if self.obs is not None:
                    # tick-granular inter-token latency: the argmax/device_get
                    # in _sample already materialized this tick's logits, so
                    # the stamp costs no extra device sync
                    now = time.perf_counter()
                    last = self._t_last_tok.get(s)
                    if last is not None:
                        self._h_intertok.observe(now - last)
                    self._t_last_tok[s] = now
                if self._emit(req, tok) or self.slot_pos[s] >= self.max_len - 1:
                    if not req.done:  # max_len frontier hit: force-finish
                        self._finish(req, "max_len")
                    if self.manager is not None:
                        self.manager.free(req.rid)
                    self.slot_req[s] = None
                    self._admit_order.remove(s)
                    self._t_last_tok.pop(s, None)
            if self.obs is not None and not self.pending():
                self._host_bound_close()


class PagedEngine(Engine):
    """Deprecated alias of :class:`Engine`.

    Every family now serves through the unified session scheduler; the old
    ring-cache reference engine is gone and ``PagedEngine`` simply forwards
    to :class:`Engine` (whose default backend for full-attention dense/moe
    is the paged block pool this class used to hard-code).
    """

    def __init__(self, *args, **kwargs):
        import warnings
        warnings.warn("PagedEngine is a deprecated alias; use serve.engine."
                      "Engine", DeprecationWarning, stacklevel=2)
        super().__init__(*args, **kwargs)
