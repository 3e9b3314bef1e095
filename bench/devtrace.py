"""From a profiler trace of the window to the per-layer numbers.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` wrote, through
``jax.profiler.ProfileData`` only:

* device planes (``/device:TPU:<n>``): the ``XLA Ops`` line gives every
  operation that ran, the ``XLA Modules`` line every execution of a
  program.  Busy time is the union of the operations' intervals inside the
  traced window, averaged over the chips.
* host planes: the benchmark's own spans (``bench/...``, see ``serve.py``)
  say what the host was doing, and carry the index of the call they
  launched.

Each execution of the prefill or decode program is paired with the call
that launched it: dispatches and executions keep their order, so the
executions seen are a run of consecutive calls, and the pairing is the
latest run whose every dispatch came before its execution started.  The
pairing gives each kernel event the rows and context lengths it worked on,
and the configuration's ops module (``KERNELS``, by default :mod:`opcount`)
the operations and bytes they need.

A device operation's name in the trace is its HLO instruction, shapes
included.  A Mosaic kernel (``custom_call_target="tpu_custom_call"``) takes
its kind from the instruction's name, which is its ``pallas_call``'s
(``%tt_linear.48 = ...`` is a ``tt_linear``); its rows and widths come from
the same text.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict
from types import SimpleNamespace

import numpy as np

import opcount

TOL_S = 50e-6            # host and device clocks agree to this
PROGRAMS = {"prefill": "jit__prefill", "decode": "jit__decode"}
_SUFFIX = re.compile(r"[.\d]+$")
_INSTR_SUFFIX = re.compile(r"(\.\d+)+$")


# -- reading the trace ---------------------------------------------------------
def load(trace_dir: str):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def events(pd) -> dict:
    """{"ops": [...], "modules": [...], "spans": [...]} with times in
    seconds on the trace's clock; ops and modules carry their chip."""
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            for e in line.events:
                rec = {"name": e.name, "start": e.start_ns * 1e-9,
                       "dur": e.duration_ns * 1e-9}
                if dev and line.name == "XLA Ops":
                    rec["chip"] = int(dev.group(1))
                    ops.append(rec)
                elif dev and line.name == "XLA Modules":
                    rec["chip"] = int(dev.group(1))
                    modules.append(rec)
                elif not dev and e.name.startswith("bench/"):
                    spans.append(rec)
    for lst in (ops, modules, spans):
        lst.sort(key=lambda r: r["start"])
    return {"ops": ops, "modules": modules, "spans": spans}


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float):
    """Gaps ``(start, end)`` in [lo, hi] where no interval runs."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def align(exec_starts, dispatch_times, tol: float = TOL_S):
    """Index of the call behind the first execution: the largest ``a`` with
    ``dispatch_times[a + i] <= exec_starts[i] + tol`` for every ``i``, or
    ``None`` when no run of calls fits."""
    n, m = len(exec_starts), len(dispatch_times)
    if n == 0 or m < n:
        return None
    e = np.asarray(exec_starts) + tol
    d = np.asarray(dispatch_times)
    for a in range(m - n, -1, -1):
        if np.all(d[a:a + n] <= e):
            return a
    return None


def clock_offset(spans, prefix: str, host_times) -> float | None:
    """Trace clock minus ``perf_counter``, from spans ``<prefix>#<i>`` whose
    call ``i`` was stamped at ``host_times[i]`` just before the span."""
    diffs = []
    for s in spans:
        if s["name"].startswith(prefix + "#"):
            i = int(s["name"].rsplit("#", 1)[1])
            if i < len(host_times):
                diffs.append(s["start"] - host_times[i])
    return float(np.median(diffs)) if diffs else None


# -- the program's kernels -----------------------------------------------------
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*(\S+?)(?:\{[^}]*\})?\s+custom-call\(")
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def _braced(line: str, key: str) -> str:
    """The text inside the balanced braces that follow ``key``."""
    i = line.find(key + "{")
    if i < 0:
        return ""
    start, depth = i + len(key), 0
    for j in range(start, len(line)):
        depth += {"{": 1, "}": -1}.get(line[j], 0)
        if depth == 0:
            return line[start + 1:j]
    return ""


def parse_kernel(text: str) -> dict | None:
    """{"kind", "result", "operands"} of a Mosaic kernel's HLO instruction,
    or ``None`` for any other instruction.  ``kind`` is the instruction's
    name without its numeric suffix."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    m = _INSTR.match(text)
    if not m:
        return None
    res = _SHAPE.match(m.group(2))
    operands = [(dt, tuple(int(x) for x in dims.split(",") if x))
                for dt, dims in _SHAPE.findall(_braced(text, "operand_layout_constraints="))]
    result = tuple(int(x) for x in res.group(2).split(",") if x) if res else ()
    return {"kind": _INSTR_SUFFIX.sub("", m.group(1)), "result": result,
            "operands": operands}


def short_name(text: str) -> str:
    """``%fusion.237 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head) or head


# -- the reduction -------------------------------------------------------------
def reduce_events(ev: dict, window, calls: dict, chips: int = 1) -> dict:
    """Per-layer quantities of one traced window.

    ``window`` is (start, end) on the trace clock; ``calls`` maps a program
    ("prefill"/"decode") to {"times": dispatch times on the trace clock,
    "info": what each call computed}.
    """
    lo, hi = window
    ops = [o for o in ev["ops"] if lo <= o["start"] < hi]
    busy = sum(union_seconds([(o["start"], o["start"] + o["dur"]) for o in ops
                              if o["chip"] == c], lo, hi)
               for c in range(chips)) / chips

    execs = {}
    for prog, mod_name in PROGRAMS.items():
        mods = [m for m in ev["modules"] if m["chip"] == 0
                and m["name"].split("(")[0] == mod_name and lo <= m["start"] < hi]
        times = calls.get(prog, {}).get("times", [])
        a = align([m["start"] for m in mods], times)
        info = calls.get(prog, {}).get("info", [])
        for i, m in enumerate(mods):
            m["call"] = info[a + i] if a is not None else None
        execs[prog] = mods

    # every op of chip 0 gets the program execution it ran in
    spans = sorted((m["start"], m["start"] + m["dur"], prog, m)
                   for prog, mods in execs.items() for m in mods)
    kev, j, parsed = [], 0, {}
    for o in ops:
        if o["chip"] != 0:
            continue
        while j < len(spans) and spans[j][1] < o["start"]:
            j += 1
        inside = j < len(spans) and spans[j][0] <= o["start"]
        o["prog"] = spans[j][2] if inside else "other"
        if o["name"] not in parsed:
            parsed[o["name"]] = parse_kernel(o["name"])
        k = parsed[o["name"]]
        o["label"] = k["kind"] if k else short_name(o["name"])
        if inside and k:
            kev.append(dict(k, prog=o["prog"], dur=o["dur"],
                            call=spans[j][3]["call"]))

    return {"window_s": hi - lo, "busy_s": busy, "execs": execs,
            "kernel_events": kev, "ops": ops}


def roofline_of(kev, kind: str, tt: dict, model: dict, peaks: dict, *,
                ops=opcount, events=None):
    """(share of roofline in %, share of calls bound by memory) of ``kind``,
    each call counted by ``ops.KERNELS[kind](event, run)``; ``None`` where
    ``ops`` counts no such kernel, or cannot count one of its calls (it
    returns ``None``), since a share of the other calls would be biased."""
    count = getattr(ops, "KERNELS", {}).get(kind)
    if count is None:
        return None
    run = SimpleNamespace(model=model, tt=tt, events=events)
    need = spent = 0.0
    mem_bound = n = 0
    for k in kev:
        if k["kind"] != kind:
            continue
        got = count(k, run)
        if got is None:
            return None
        n_ops, moved = got
        t_ops, t_mem = n_ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"]
        need += max(t_ops, t_mem)
        mem_bound += t_mem >= t_ops
        spent += k["dur"]
        n += 1
    if n == 0 or spent <= 0:
        return None
    return 100.0 * need / spent, mem_bound / n


def useful_flops(execs: dict, model: dict, tt: dict, ops=opcount) -> float | None:
    """Operations the served model needs for the tokens the traced
    executions processed (``ops.sequence_flops``): every real prompt token
    through every block, one unembedding per finished prompt, and every
    decoded token with its unembedding (padding rows and the other prefill
    logits excluded)."""
    total = 0
    for prog, mods in execs.items():
        for m in mods:
            call = m.get("call")
            if call is None:
                return None
            if prog == "prefill":
                ctx = [s + i + 1 for s, n in call["chunks"] for i in range(n)]
                total += ops.sequence_flops(model, tt, ctx, call["finishing"])
            else:
                ctx = [int(p) + 1 for p in call["positions"] if p >= 0]
                total += ops.sequence_flops(model, tt, ctx, len(ctx))
    return float(total)


def breakdown(red: dict, spans, window, limit: int = 10) -> dict:
    """Top device operations by time (grouped by program and operation, the
    kernels by kind) and the longest idle gaps, each gap named by the
    benchmark span the host was in at its middle."""
    lo, hi = window
    by_name = defaultdict(float)
    for o in red["ops"]:
        if o["chip"] == 0:
            by_name[f"{o['prog']}:{o['label']}"] += o["dur"]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:limit]
    gaps = idle_gaps([(o["start"], o["start"] + o["dur"]) for o in red["ops"]
                      if o["chip"] == 0], lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:limit]
    named_gaps = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inside = [sp for sp in spans if sp["start"] <= mid <= sp["start"] + sp["dur"]]
        label = min(inside, key=lambda sp: sp["dur"])["name"].split("#")[0] \
            if inside else "no benchmark span"
        named_gaps.append([label, e - s])
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": named_gaps}


# -- the harness's entry -------------------------------------------------------
def prefill_calls(prefills, chunk: int):
    """Per prefill-chunk call, in order: {"chunks": [(start, n)] of the rows
    that hold real tokens, "finishing": prompts whose last token it holds}."""
    out = []
    for _, lens in prefills:
        n_chunks = -(-max(lens) // chunk)
        for c in range(n_chunks):
            rows = [(c * chunk, min(n - c * chunk, chunk)) for n in lens
                    if n > c * chunk]
            fin = sum(1 for n in lens if c * chunk < n <= (c + 1) * chunk)
            out.append({"chunks": rows, "finishing": fin})
    return out


def reduce_run(trace_dir, engine, geo, run, host_window) -> dict:
    """The reduction of a traced run, handed to the metric readers."""
    pd = load(trace_dir)
    ev = events(pd)
    pref = prefill_calls(engine.prefills, geo["prefill_chunk"])
    t_pref = [t for t in engine.prefill_times]
    dec = [{"positions": p} for _, p in engine.decodes]
    t_dec = [t for t, _ in engine.decodes]
    off = clock_offset(ev["spans"], "bench/decode_dispatch", t_dec)
    if off is None:
        off = clock_offset(ev["spans"], "bench/prefill_chunk", t_pref)
    if off is None:
        raise RuntimeError("the trace holds none of the benchmark's spans")
    window = (host_window[0] + off, host_window[1] + off)
    calls = {"prefill": {"times": [t + off for t in t_pref], "info": pref},
             "decode": {"times": [t + off for t in t_dec], "info": dec}}

    red = reduce_events(ev, window, calls, chips=run.cell["chips"])
    red["breakdown"] = breakdown(red, ev["spans"], window)
    return red


# -- what the metric readers call ----------------------------------------------
def idle_share(trace: dict | None):
    if not trace or trace["window_s"] <= 0 or not trace["ops"]:
        return None  # a trace with no device operation was not read right
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def program_ms(trace: dict | None, prog: str):
    if not trace or not trace["execs"].get(prog):
        return None
    return 1e3 * float(np.mean([m["dur"] for m in trace["execs"][prog]]))


def roofline(run, kind: str):
    if not run.trace:
        return None
    got = roofline_of(run.trace["kernel_events"], kind, run.tt, run.model,
                      run.peaks, ops=run.ops, events=run.events)
    if got is None:
        return None
    share, mem = got
    print(f"[roofline] {kind}: {share:.3f}% of its roofline; "
          f"{100 * mem:.1f}% of calls bound by HBM bandwidth", flush=True,
          file=sys.stderr)
    return share


def mfu(run):
    if not run.trace:
        return None
    flops = useful_flops(run.trace["execs"], run.model, run.tt, run.ops)
    if not flops or run.trace["window_s"] <= 0:
        return None
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["bf16_flops"])
