"""The program's own spans in a traced run, read from its event log.

A traced run turns the program's observer on, and ``run.events`` holds what
it recorded (``repro.obs``): the scheduler's events (``submit``,
``first_token``, ...) and one ``span`` event per timed region of the
serving loop, with its ``name``, start ``t`` on ``perf_counter``, ``dur``,
``sid``, ``parent`` and fields.  The readers here look at the traced third
of the window (``traced_interval``): the part the profiler records, which
ends before ``jax.profiler.stop_trace`` stalls the event loop.

The two medians (``queue_wait_p50_ms``, ``prefill_wall_p50_ms``) read the
scheduler's ``submit``, ``admit`` and ``first_token`` events, which every
version of the program with an observer records.  The rest reads spans: a
program that records none reads ``None`` there, and ``report`` prints
nothing.
"""
from __future__ import annotations

import sys
from collections import defaultdict

from stats import nearest_rank

OVERLAYS = ("serve/host_bound", "serve/pump_idle")  # spans that do not nest


def traced_interval(run) -> tuple[float, float]:
    """``[lo, hi)`` on ``perf_counter``: the middle third of the window, at
    most 10 s, that ``harness._Tracer`` profiles."""
    lo = run.t0 + run.seconds / 3.0
    return lo, lo + min(10.0, run.seconds / 3.0)


def spans(run, name: str | None = None) -> list[dict]:
    return [e for e in run.events or () if e["ev"] == "span"
            and (name is None or e["name"] == name)]


def _clip(e: dict, lo: float, hi: float) -> float:
    return max(0.0, min(e["t"] + e["dur"], hi) - max(e["t"], lo))


def requests(run) -> dict[int, dict] | None:
    """Per request submitted in ``[t0, hi)`` whose first ``admit`` event
    (stamped as its batch's prefill begins) lies before ``hi``: its
    ``submit``, ``admit`` and ``first`` (token) stamps.  ``None`` when the
    program recorded no admission.

    Later requests are left out: ``stop_trace`` blocks the event loop from
    ``hi`` on, so those due meanwhile are submitted late, all at once, and
    queue behind each other; the ones due after it are held up by that
    backlog.  Only a trace that does not stall the loop lets the set
    widen to the whole window."""
    admits = [e for e in run.events or () if e["ev"] == "admit"]
    if not admits:
        return None
    hi = traced_interval(run)[1]
    admit = {}
    for a in sorted(admits, key=lambda e: e["t"]):
        admit.setdefault(a["rid"], a["t"])
    first = {e["rid"]: e["t"] for e in run.events if e["ev"] == "first_token"}
    return {e["rid"]: {"submit": e["t"], "admit": admit[e["rid"]],
                       "first": first[e["rid"]]}
            for e in run.events if e["ev"] == "submit"
            and run.t0 <= e["t"] < hi and admit.get(e["rid"], hi) < hi
            and e["rid"] in first}


def p50_ms(run, start: str, end: str) -> float | None:
    """Nearest-rank median of ``end - start`` over ``requests``, in ms."""
    reqs = requests(run)
    if not reqs:
        return None
    return 1e3 * nearest_rank([r[end] - r[start] for r in reqs.values()], 50)


def host_bound_share(run) -> float | None:
    """Percent of the traced interval under ``serve/host_bound``."""
    if not spans(run):
        return None
    lo, hi = traced_interval(run)
    return 100.0 * sum(_clip(e, lo, hi) for e in spans(run, "serve/host_bound")) \
        / (hi - lo)


def ahead_starved_share(run) -> float | None:
    """Percent of the dispatch-ahead decode dispatches in the traced
    interval that found their predecessor already finished."""
    lo, hi = traced_interval(run)
    ahead = [e for e in spans(run, "serve/decode_dispatch")
             if e.get("ahead") and lo <= e["t"] < hi]
    if not ahead:
        return None
    return 100.0 * sum(bool(e["starved"]) for e in ahead) / len(ahead)


def self_times(run) -> dict[str, float]:
    """Seconds of the traced interval by innermost span: a nested span's
    time less its children's, the overlays whole, and ``no span`` for the
    rest of the pump's thread."""
    lo, hi = traced_interval(run)
    out: dict[str, float] = defaultdict(float)
    child = defaultdict(float)
    nested = [e for e in spans(run) if e["name"] not in OVERLAYS]
    for e in nested:
        if e["parent"] >= 0:
            child[e["parent"]] += _clip(e, lo, hi)
    top = 0.0
    for e in nested:
        own = _clip(e, lo, hi)
        out[e["name"]] += own - child[e["sid"]]
        if e["parent"] < 0:
            top += own
    idle = sum(_clip(e, lo, hi) for e in spans(run, "serve/pump_idle"))
    out["serve/pump_idle"] = idle
    out["no span"] = max(0.0, (hi - lo) - top - idle)
    return dict(out)


def ttft_split(run) -> dict | None:
    """The median-TTFT request of ``requests`` (client stamps), its TTFT
    split into generator lateness, queue wait, prefill and delivery."""
    reqs = requests(run)
    if not reqs:
        return None
    recs = [r for r in run.records if r.rid in reqs and r.stamps]
    if not recs:
        return None
    ttft = {r.rid: r.stamps[0] - r.due for r in recs}
    p50 = nearest_rank(list(ttft.values()), 50)
    rec = next(r for r in recs if ttft[r.rid] == p50)
    q = reqs[rec.rid]
    return {"rid": rec.rid, "ttft": p50, "lateness": q["submit"] - rec.due,
            "queue": q["admit"] - q["submit"], "prefill": q["first"] - q["admit"],
            "delivery": rec.stamps[0] - q["first"]}


def report(run) -> None:
    """One stderr line: the traced interval's self time by span, the
    host-bound overlay and its share, the share of starved dispatch-ahead
    ticks, compiles in it, and the median TTFT's parts."""
    if not spans(run):
        return
    lo, hi = traced_interval(run)
    parts = sorted(self_times(run).items(), key=lambda kv: -kv[1])
    hb = sum(_clip(e, lo, hi) for e in spans(run, "serve/host_bound"))
    starved = ahead_starved_share(run)
    compiles = [e["fun"] for e in run.events if e["ev"] == "compile"
                and e["stage"] == "compile" and lo <= e["t"] < hi]
    split = ttft_split(run)
    n = len(requests(run) or ())
    ttft = "none" if split is None else (
        f"over {n} requests: rid {split['rid']} {1e3 * split['ttft']:.3f} ms = lateness "
        f"{1e3 * split['lateness']:.3f} + queue {1e3 * split['queue']:.3f} + "
        f"prefill {1e3 * split['prefill']:.3f} + delivery "
        f"{1e3 * split['delivery']:.3f} ms")
    print(f"[spans] traced {hi - lo:.3f} s, self time: "
          + ", ".join(f"{k} {v:.4f}" for k, v in parts if v > 0)
          + f"; serve/host_bound {hb:.4f} s ({host_bound_share(run):.3f} %); "
          + "starved ahead dispatches "
          + ("none" if starved is None else f"{starved:.3f} %") + "; compiles: "
          + (", ".join(compiles) or "none") + f"; TTFT p50 {ttft}",
          file=sys.stderr, flush=True)
