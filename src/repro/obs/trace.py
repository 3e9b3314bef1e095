"""Structured scheduler event trace and the spans of the serving loop.

The serving engine narrates its scheduling decisions as a flat stream of
dict events — one per admit / prefill chunk / decode tick / preemption /
cancel / deadline miss / finish / pool sample — each stamped with a
**monotonic** timestamp
(``time.perf_counter``; wall-clock never enters duration math, DESIGN.md §9)
and a process-wide sequence number.  The stream is the ground truth the
ordering-invariant tests replay (submit ≤ admit ≤ first token ≤ finish;
every preempt is followed by a re-admission), and ``repro.obs.export``
validates and persists it as JSONL.

A :class:`Span` times one region of the serving loop and records it as a
``span`` event when it closes.  It also wraps the region in a
``jax.profiler.TraceAnnotation`` named ``<name>#<sid>``, so a profiler
trace and the event log pair span for span by ``sid``: the annotation's
start minus the event's ``t`` is the offset between the trace's clock and
``perf_counter``, the same for every span.
"""
from __future__ import annotations

import itertools
import time

# Event types and their required per-type fields (beyond the common
# ``ev`` / ``t`` / ``seq``).  ``repro.obs.export.EVENT_SCHEMA`` builds the
# full field-type map from this table.
EVENT_FIELDS: dict[str, tuple[str, ...]] = {
    "submit": ("rid", "prompt_len", "max_tokens"),
    "admit": ("rid", "slot", "tick", "n_tokens"),
    "prefill_chunk": ("tick", "chunk", "n_chunks", "rids", "tile_rows",
                      "real_rows"),
    "first_token": ("rid", "tick", "ttft_s"),
    "decode_tick": ("tick", "active"),
    "preempt": ("rid", "slot", "tick"),
    "cancel": ("rid", "slot", "tick", "reason"),
    "deadline_miss": ("rid", "tick", "deadline_s"),
    "finish": ("rid", "tick", "reason", "n_out"),
    "pool_sample": ("tick", "utilization", "free_blocks", "live_tokens",
                    "active_slots"),
    # one timed region (``t`` is its start); spans may also carry the
    # optional fields of ``SPAN_FIELDS``
    "span": ("name", "dur", "sid", "parent"),
    # a jaxpr trace or backend compile, inside span ``sid`` (-1: none)
    "compile": ("fun", "stage", "dur", "sid"),
}
# fields a span carries where they apply, checked by type when present
SPAN_FIELDS = ("rids", "n_tokens", "chunk", "n_chunks", "tile_rows",
               "real_rows", "tick", "active", "ahead", "starved")

_seq = itertools.count()
_sid = itertools.count()


class Trace:
    """Append-only event log with monotonic timestamps.

    ``writer`` (anything with a ``write(dict)`` method — see
    ``export.JsonlWriter``) receives every event as it is emitted; ``keep``
    retains events in memory for in-process inspection (the default — the
    fuzz replays read ``trace.events`` directly).
    """

    def __init__(self, writer=None, keep: bool = True):
        self.events: list[dict] = []
        self._writer = writer
        self._keep = keep

    def emit(self, ev: str, t: float | None = None, **fields) -> dict:
        """Record one event; ``t`` defaults to ``perf_counter()`` now but may
        be passed in so an event reuses a timestamp already taken (e.g. the
        post-``block_until_ready`` TTFT stamp)."""
        rec = {"ev": ev, "t": time.perf_counter() if t is None else t,
               "seq": next(_seq), **fields}
        if self._keep:
            self.events.append(rec)
        if self._writer is not None:
            self._writer.write(rec)
        return rec

    def by_type(self, ev: str) -> list[dict]:
        return [e for e in self.events if e["ev"] == ev]

    def close(self) -> None:
        if self._writer is not None and hasattr(self._writer, "close"):
            self._writer.close()


class Span:
    """One timed region, recorded as a ``span`` event when it closes.

    Opening draws ``sid`` from a counter of its own (``seq`` keeps emission
    order), enters ``jax.profiler.TraceAnnotation(f"{name}#{sid}")`` and
    stamps ``t``.  Given a ``stack`` (a ``with`` block), the span nests:
    it is pushed while open, and spans opened inside it name it
    ``parent``.  Without one it is an overlay, opened and closed at two
    points of the loop that need not nest (``Observer.overlay``), with
    parent -1.  ``fields`` may be filled in while the span is open.
    """

    __slots__ = ("_trace", "_stack", "name", "fields", "sid", "parent", "t",
                 "_ann")

    def __init__(self, trace: Trace, stack: list | None, name: str,
                 fields: dict):
        self._trace = trace
        self._stack = stack
        self.name = name
        self.fields = fields

    def open(self) -> "Span":
        import jax.profiler  # local: the metrics path never pulls in jax

        self.sid = next(_sid)
        stack = self._stack
        self.parent = stack[-1].sid if stack else -1
        if stack is not None:
            stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(f"{self.name}#{self.sid}")
        self._ann.__enter__()
        self.t = time.perf_counter()
        return self

    def close(self) -> dict:
        dur = time.perf_counter() - self.t
        self._ann.__exit__(None, None, None)
        if self._stack is not None:
            self._stack.pop()
        return self._trace.emit("span", t=self.t, name=self.name, dur=dur,
                                sid=self.sid, parent=self.parent,
                                **self.fields)

    __enter__ = open

    def __exit__(self, *exc) -> None:
        self.close()
