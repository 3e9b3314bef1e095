"""Load drivers: the client side of a run, on the program's async front-end.

The open loop drives ``repro.serve.frontend.AsyncEngine`` (dispatch-ahead on)
over a :class:`RecordingEngine`, the program's ``Engine`` with benchmark-side
spans and records around the calls into each layer:

* ``bench/admit`` around admission (batched chunked prefill), recording the
  token count of every sequence it prefilled;
* ``bench/prefill_chunk#<i>`` around the launch of each prefill chunk;
* ``bench/decode_dispatch#<i>`` around each decode launch, recording the
  positions of the tick;
* ``bench/decode_collect`` around each collection.

The spans show in a profiler trace as host activity; the records let the
trace reduction know what each device execution computed.  The hooks
override private methods of ``Engine``; where a change to the program takes
them off its path, a window that served tokens records no call, and the
harness fails the run (``hooks_silent``) rather than read metrics without
their pairing.  Every stamp a
metric uses is the client's: ``time.perf_counter()`` when a token reaches
the consumer coroutine.  An open loop times each request from when it was
due, so a stall of the event loop delays the clock of every request due
behind it.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.serve.engine import Engine
from repro.serve.frontend import AsyncEngine

from traffic import Request


class RecordingEngine(Engine):
    """``Engine`` with benchmark spans and records at its layer boundaries."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.prefills: list[tuple[float, list[int]]] = []
        self.prefill_times: list[float] = []
        self.decodes: list[tuple[float, np.ndarray]] = []
        self.prefill_fn = self._prefill

        def prefill_chunk(*args):
            i = len(self.prefill_times)
            self.prefill_times.append(time.perf_counter())
            with jax.profiler.TraceAnnotation(f"bench/prefill_chunk#{i}"):
                return self.prefill_fn(*args)

        self._prefill = prefill_chunk

    def clear_records(self) -> None:
        self.prefills.clear()
        self.prefill_times.clear()
        self.decodes.clear()

    def _admit(self):
        waiting = [(r, len(r.prompt) + len(r.out_tokens)) for r in self.queue]
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/admit"):
            super()._admit()
        left = {id(r) for r in self.queue}
        lens = [n for r, n in waiting if id(r) not in left]
        if lens:
            self.prefills.append((t, lens))

    def _decode_dispatch(self, plan, device_toks=None):
        i = len(self.decodes)
        self.decodes.append((time.perf_counter(), plan.positions.copy()))
        with jax.profiler.TraceAnnotation(f"bench/decode_dispatch#{i}"):
            return super()._decode_dispatch(plan, device_toks=device_toks)

    def _decode_collect(self, plan, logits, toks_host=None):
        with jax.profiler.TraceAnnotation("bench/decode_collect"):
            return super()._decode_collect(plan, logits, toks_host=toks_host)


@dataclass
class Record:
    """What one client saw of one request."""

    req: Request
    due: float                      # perf_counter when it was due
    submitted: float = 0.0
    rid: int = -1
    stamps: list[float] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)
    finished: bool = False          # served to its max_tokens (or max_len)
    error: str = ""


async def _consume(handle, rec: Record):
    try:
        async for tok in handle.stream():
            rec.stamps.append(time.perf_counter())
            rec.tokens.append(tok)
        rec.finished = handle.done and not handle.cancelled
    except Exception as e:  # a failed request counts as missing, not fatal
        rec.error = f"{type(e).__name__}: {e}"


def _submit(front: AsyncEngine, rec: Record):
    rec.submitted = time.perf_counter()
    handle = front.submit(rec.req.prompt, max_tokens=rec.req.max_tokens)
    rec.rid = handle.rid
    return handle


async def open_loop(front: AsyncEngine, schedule: list[Request],
                    seconds: float, wait_s: float, on_window=None):
    """Send ``schedule`` at its due times; returns (records of the counted
    requests, window start, window end).  Counted requests are waited for
    up to ``wait_s`` past the window; then everything left is cancelled."""
    loop_tasks: list[asyncio.Task] = []
    handles = []
    counted: list[Record] = []
    t0 = time.perf_counter()
    end = t0 + seconds
    deadline = end + wait_s
    if on_window is not None:
        on_window(t0, end)

    def counted_done() -> bool:
        return all(r.finished or r.error for r in counted) and \
            len(counted) == sum(q.counted for q in schedule)

    for req in schedule:
        due = t0 + req.due
        if not req.counted and counted_done():
            break
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        if time.perf_counter() > deadline:
            break
        rec = Record(req=req, due=due)
        handle = _submit(front, rec)
        handles.append(handle)
        loop_tasks.append(asyncio.create_task(_consume(handle, rec)))
        if req.counted:
            counted.append(rec)
    while not counted_done() and time.perf_counter() < deadline:
        await asyncio.sleep(0.01)
    for h in handles:
        h.cancel()
    await front.drain()
    await asyncio.gather(*loop_tasks)
    return counted, t0, end


async def warm_up(front: AsyncEngine, requests: list[Request]) -> list[Record]:
    """Serve ``requests`` at once and wait for them: compiles (or loads)
    every program the window runs."""
    recs = [Record(req=r, due=time.perf_counter()) for r in requests]
    await asyncio.gather(*(_consume(_submit(front, rec), rec) for rec in recs))
    await front.drain()
    return recs
