"""Asyncio serving front-end: submit / stream / cancel over the engine.

:class:`AsyncEngine` wraps the unified continuous-batching
:class:`~repro.serve.engine.Engine` behind an asyncio surface (DESIGN.md
§12): ``submit()`` returns a :class:`RequestHandle` immediately, tokens
arrive through ``async for tok in handle.stream()`` as the scheduler emits
them, ``handle.cancel()`` frees the request's slot and blocks mid-flight,
and ``submit(deadline_s=)`` rides the engine's deadline expiry.  One
background *pump* task drives the engine; consumers are ordinary coroutines
on the same event loop.

**Dispatch-ahead double buffering.**  The engine's decode tick is
schedule → dispatch → collect, and jax dispatch is asynchronous: launching
tick *N* returns logits immediately while the device computes.  When every
in-flight slot is guaranteed to survive its emission (greedy sampling, no
eos watch, away from the max_tokens/max_len frontier, pool growth without
preemption — ``Engine._plan_ahead``), the pump samples tick *N*'s tokens
with a **device-side argmax** and dispatches tick *N+1* from that device
array before anything touches the host.  Tick *N*'s tokens are then pulled
to host, bookkeeping runs, and stream consumers get their tokens — all
while the device is busy with tick *N+1*.  When the guarantee fails (a
request near its frontier, a pending cancel, a waiting admission), the pump
falls back to the synchronous collect-then-dispatch order, so emitted
tokens are **bitwise identical** to the synchronous engine either way
(``tests/test_frontend.py`` fuzzes this under Poisson arrivals with random
cancellations).

Invariants the pump maintains (the dispatch-ahead contract):

* at most one tick is in flight at any time (double buffering, not a queue);
* cancellations, deadline expiry, and admissions are applied only while no
  tick is in flight — a cancel arriving mid-flight is applied before the
  *next* dispatch, and collection skips slots whose occupant changed;
* an in-flight ahead tick only ever extends sequences the collect of its
  predecessor cannot finish, so no token is ever emitted for a dead request.

The front-end is drained-reusable: the pump exits when the engine drains
and a later ``submit`` starts a fresh one.

With obs on, the pump's own stretches are spans beside the engine's
(DESIGN.md §9): ``serve/deliver``, ``serve/token_pull`` (the acknowledged
sync of the dispatch-ahead path), ``serve/yield`` (each ``await`` that
hands the loop to consumers and the load generator), and the overlay
``serve/pump_idle``, from the pump's exit on drain to the ``submit`` that
restarts it.
"""
from __future__ import annotations

import asyncio
import contextlib
from typing import AsyncIterator

import numpy as np

from . import steps
from .engine import Engine, Request

_DONE = object()  # stream sentinel
_NULL_CTX = contextlib.nullcontext()  # reusable no-op span (obs disabled)


class RequestHandle:
    """One submitted request: stream its tokens, await it, or cancel it."""

    def __init__(self, owner: "AsyncEngine", req: Request):
        self._owner = owner
        self.req = req
        self._queue: asyncio.Queue = asyncio.Queue()
        self._done = asyncio.Event()
        self._n_sent = 0
        self._cancel_requested = False
        self._error: BaseException | None = None

    @property
    def rid(self) -> int:
        return self.req.rid

    @property
    def out_tokens(self) -> list[int]:
        return list(self.req.out_tokens)

    @property
    def done(self) -> bool:
        return self.req.done

    @property
    def cancelled(self) -> bool:
        return self.req.cancelled

    @property
    def finish_reason(self) -> str:
        return self.req.finish_reason

    async def stream(self) -> AsyncIterator[int]:
        """Yield token ids as the scheduler emits them; ends at finish or
        cancellation (check :attr:`cancelled` to distinguish)."""
        while True:
            item = await self._queue.get()
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    async def wait_done(self) -> None:
        """Wait for finish/cancellation without consuming the stream (the
        traffic runner's cancel timers race this against their delay)."""
        await self._done.wait()

    async def result(self) -> list[int]:
        """Wait for the request to finish; returns all emitted tokens."""
        await self._done.wait()
        if self._error is not None:
            raise self._error
        return list(self.req.out_tokens)

    def cancel(self) -> None:
        """Request cancellation; applied by the pump at the next safe point
        (between in-flight ticks).  Idempotent; a no-op after finish."""
        if self.req.done or self._cancel_requested:
            return
        self._cancel_requested = True
        self._owner._cancel_q.append(self)


class AsyncEngine:
    """Asyncio front-end over the unified serving engine.

    Construct exactly like :class:`~repro.serve.engine.Engine` (model/params
    plus geometry kwargs), or wrap a prebuilt engine with ``engine=``.
    ``submit`` must be called from a running event loop — it lazily starts
    the pump task that drives scheduling.  ``dispatch_ahead=False`` pins the
    pump to the synchronous collect-then-dispatch order (the fuzz suite's
    control arm).
    """

    def __init__(self, model=None, params=None, *, engine: Engine | None = None,
                 dispatch_ahead: bool = True, **engine_kwargs):
        if engine is not None:
            if model is not None or params is not None or engine_kwargs:
                raise ValueError("pass either a prebuilt engine= or "
                                 "model/params + engine kwargs, not both")
            self.engine = engine
        else:
            self.engine = Engine(model, params, **engine_kwargs)
        self.dispatch_ahead = dispatch_ahead
        self._idle = None  # open serve/pump_idle overlay span (obs on)
        self._handles: dict[int, RequestHandle] = {}
        self._cancel_q: list[RequestHandle] = []
        self._pump_task: asyncio.Task | None = None

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: list[int], max_tokens: int = 32,
               eos: int | None = None, enc_frames=None,
               deadline_s: float | None = None) -> RequestHandle:
        """Validate + enqueue a request and (re)start the pump.

        Raises the engine's submit-time ``ValueError``s (empty prompt,
        non-positive ``max_tokens``/``deadline_s``, a request the pool could
        never hold) before any handle exists."""
        loop = asyncio.get_running_loop()  # raises outside an event loop
        req = self.engine.submit(prompt, max_tokens=max_tokens, eos=eos,
                                 enc_frames=enc_frames, deadline_s=deadline_s)
        handle = RequestHandle(self, req)
        self._handles[req.rid] = handle
        if self._pump_task is None or self._pump_task.done():
            # drained-engine reuse: a finished pump is replaced, never left
            # silently stale
            if self._idle is not None:
                self._idle.close()
                self._idle = None
            self._pump_task = loop.create_task(self._pump())
        return handle

    async def drain(self) -> None:
        """Wait until every submitted request has finished (or cancelled);
        re-raises a pump failure."""
        while self._pump_task is not None and not self._pump_task.done():
            await asyncio.shield(self._pump_task)

    def close(self) -> None:
        """Abandon the pump (outstanding streams get the cancellation)."""
        if self._pump_task is not None and not self._pump_task.done():
            self._pump_task.cancel()

    # -- pump -----------------------------------------------------------------
    async def _pump(self) -> None:
        eng = self.engine
        in_flight: tuple | None = None  # (plan, logits) — at most one tick
        idle = 0
        drained = False
        try:
            while True:
                if in_flight is None:
                    self._apply_cancels()
                    eng._expire_deadlines()
                    self._deliver()
                    if not eng.pending():
                        drained = True
                        break
                    eng._admit()  # batched chunked prefill (device-blocking)
                    self._deliver()  # prefill emitted first tokens
                    await self._yield()
                    plan = eng._decode_schedule()
                    if plan is None:
                        eng._finish_tick()
                        idle += 1
                        if idle > 10_000:
                            raise RuntimeError("async pump stalled: queue "
                                               "blocked with no active slots")
                        continue
                    idle = 0
                    in_flight = (plan, eng._decode_dispatch(plan))
                    # consumers run while the device computes this tick
                    await self._yield()
                    continue
                plan, logits = in_flight
                in_flight = None
                plan2 = None
                if self.dispatch_ahead and not self._cancel_q and \
                        not (eng.queue and None in eng.slot_req) and \
                        not eng._deadline_due():
                    # no pending cancel, no admission waiting on a free slot,
                    # no expired deadline: chain the next tick ahead of
                    # collection
                    plan2 = eng._plan_ahead(plan)
                if plan2 is not None:
                    with (eng.obs.span("serve/argmax")
                          if eng.obs is not None else _NULL_CTX):
                        toks_dev = steps.greedy_tokens(logits)
                    logits2 = eng._decode_dispatch(plan2, device_toks=toks_dev)
                    # pull tick N's tokens to host while tick N+1 computes
                    with (eng.obs.span("serve/token_pull")
                          if eng.obs is not None else _NULL_CTX):
                        # analyze: allow[host-sync] the acknowledged sync: overlapped with the in-flight tick
                        toks_host = np.asarray(toks_dev)[:, 0]
                    eng._decode_collect(plan, logits, toks_host=toks_host)
                    in_flight = (plan2, logits2)
                else:
                    eng._decode_collect(plan, logits)
                eng._finish_tick()
                self._deliver()
                await self._yield()
        except BaseException as e:
            self._fail(e)
            raise
        finally:
            self._deliver()
            if eng.obs is not None:
                eng._host_bound_close()
                if drained:
                    self._idle = eng.obs.overlay("serve/pump_idle")

    async def _yield(self) -> None:
        """Hand the loop to consumers and the load generator for one turn."""
        if self.engine.obs is None:
            await asyncio.sleep(0)
            return
        with self.engine.obs.span("serve/yield"):
            await asyncio.sleep(0)

    def _apply_cancels(self) -> None:
        q, self._cancel_q = self._cancel_q, []
        for handle in q:
            self.engine.cancel(handle.req, reason="user")

    def _deliver(self) -> None:
        """Push newly emitted tokens (and completions) to consumer queues."""
        obs = self.engine.obs
        with (obs.span("serve/deliver") if obs is not None else _NULL_CTX):
            finished = []
            for rid, handle in self._handles.items():
                out = handle.req.out_tokens
                while handle._n_sent < len(out):
                    handle._queue.put_nowait(out[handle._n_sent])
                    handle._n_sent += 1
                if handle.req.done:
                    handle._queue.put_nowait(_DONE)
                    handle._done.set()
                    finished.append(rid)
            for rid in finished:
                del self._handles[rid]

    def _fail(self, error: BaseException) -> None:
        """Propagate a pump failure to every live consumer."""
        for handle in self._handles.values():
            handle._error = error
            handle._queue.put_nowait(error)
            handle._done.set()
        self._handles.clear()
