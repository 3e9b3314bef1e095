"""Spans of the serving loop (DESIGN.md §9).

``Observer.span`` records one ``span`` event per timed region (``sid``,
``parent``, ``t``, ``dur``) and wraps the region in a
``jax.profiler.TraceAnnotation`` named ``<name>#<sid>``.  These tests pin
the primitive (nesting, ids, annotation names, schema), the off switch
(nothing recorded, no annotation built), and what the spans of an
``AsyncEngine`` run on a tiny model must say: the pump's top-level spans
tile its wall time, ``serve/host_bound`` is never open while a decode tick
is in flight and covers the host work before a launch, ``serve/pump_idle``
covers a drain-then-submit gap, and a recompile is named with the span it
happened in.
"""
import asyncio
import time

import jax
import pytest
import test_serve_fuzz as fuzz

from repro.obs import Observer, validate_events
from repro.serve import AsyncEngine
from repro.serve.engine import Engine

OVERLAYS = ("serve/host_bound", "serve/pump_idle")


class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that logs names and
    the order of enters and exits."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


@pytest.fixture
def annotations(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    return ann


def _spans(obs, name=None):
    return [e for e in obs.trace.events if e["ev"] == "span"
            and (name is None or e["name"] == name)]


def test_span_nesting_ids_annotation_and_schema(annotations):
    obs = Observer()
    with obs.span("outer", tick=3) as outer:
        with obs.span("inner", rids=[1, 2]):
            pass
        outer.fields["ahead"] = True
    lay = obs.overlay("lay")
    with obs.span("after"):
        pass
    lay.close()
    evs = _spans(obs)
    by = {e["name"]: e for e in evs}
    # emitted as each closes; seq keeps that order, t is each start
    assert [e["name"] for e in evs] == ["inner", "outer", "after", "lay"]
    assert by["outer"]["parent"] == -1
    assert by["inner"]["parent"] == by["outer"]["sid"]
    assert by["lay"]["parent"] == -1
    assert by["after"]["parent"] == -1  # an overlay is on no stack
    assert len({e["sid"] for e in evs}) == 4
    assert by["outer"]["t"] <= by["inner"]["t"]
    assert by["outer"]["dur"] >= by["inner"]["dur"] >= 0
    assert by["outer"]["tick"] == 3 and by["outer"]["ahead"] is True
    assert by["inner"]["rids"] == [1, 2]
    # one annotation per span, named <name>#<sid>, entered before the stamp
    names = [n for kind, n in annotations.log if kind == "enter"]
    assert names == [f"{n}#{by[n]['sid']}" for n in ("outer", "inner", "lay", "after")]
    assert annotations.log[:4] == [("enter", f"outer#{by['outer']['sid']}"),
                                   ("enter", f"inner#{by['inner']['sid']}"),
                                   ("exit", f"inner#{by['inner']['sid']}"),
                                   ("exit", f"outer#{by['outer']['sid']}")]
    assert obs._stack == []
    assert validate_events(obs.trace.events) == []


def test_span_fields_are_type_checked():
    obs = Observer()
    with obs.span("s", ahead=True, starved=False, tick=1):
        pass
    assert validate_events(obs.trace.events) == []
    bad = dict(_spans(obs)[0], ahead=1, tick=True)
    errors = validate_events([bad])
    assert any("'ahead'" in e for e in errors)
    assert any("'tick'" in e for e in errors)
    missing = {k: v for k, v in _spans(obs)[0].items() if k != "sid"}
    assert any("missing field 'sid'" in e for e in validate_events([missing]))


def test_obs_off_records_nothing_and_builds_no_annotation(annotations):
    model, params, _ = fuzz._setup("dense")

    async def scenario():
        fe = AsyncEngine(model, params, slots=2, max_len=96, block_size=8,
                         prefill_chunk=8, obs=False)
        hs = [fe.submit([3, 1, 4, 1, 5][:k + 2], max_tokens=6) for k in range(3)]
        await fe.drain()
        return fe, hs

    fe, hs = asyncio.run(scenario())
    assert all(h.done for h in hs)
    assert fe.engine.obs is None
    eng = Engine(model, params, slots=2, max_len=96, block_size=8,
                 prefill_chunk=8, obs=False)
    eng.submit([2, 7, 1], max_tokens=4)
    eng.run()
    assert annotations.log == []


async def _serve(fe, prompts, max_tokens):
    hs = [fe.submit(p, max_tokens=max_tokens) for p in prompts]
    await asyncio.gather(*(h.result() for h in hs))
    await fe.drain()
    return hs


def _pump_run(obs, since_seq):
    return [e for e in _spans(obs) if e["seq"] > since_seq]


def test_pump_spans_tile_the_pump_and_host_bound_never_overlaps_a_tick():
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    prompts = [[(7 * k + j) % 200 + 1 for j in range(20 + k)] for k in range(8)]

    async def scenario():
        # the kernels in interpret mode give each tick device time to wait
        # on, as on the chip, where the pump's own host work is a small part
        fe = AsyncEngine(model, params, slots=4, max_len=128, block_size=8,
                         prefill_chunk=16, kernel_backend="pallas-interpret",
                         obs=obs)
        await _serve(fe, prompts, 12)  # warm: every program compiled
        mark = obs.trace.events[-1]["seq"]
        await _serve(fe, prompts, 12)
        return mark

    mark = asyncio.run(scenario())
    spans = _pump_run(obs, mark)
    assert validate_events(obs.trace.events) == []
    top = [e for e in spans if e["parent"] == -1 and e["name"] not in OVERLAYS]
    lo = min(e["t"] for e in top)
    hi = max(e["t"] + e["dur"] for e in top)
    covered = sum(e["dur"] for e in top)
    assert covered <= (hi - lo) * (1 + 1e-6)  # top-level spans never overlap
    assert covered >= 0.95 * (hi - lo), f"spans cover {covered / (hi - lo):.3f}"
    names = {e["name"] for e in top}
    assert {"serve/admit", "serve/yield", "serve/deliver", "serve/decode_schedule",
            "serve/decode_dispatch", "serve/decode_collect",
            "serve/argmax", "serve/token_pull"} <= names
    admit_children = {e["name"] for e in spans
                      if e["parent"] in {a["sid"] for a in spans
                                         if a["name"] == "serve/admit"}}
    assert admit_children == {"serve/prefill_chunk", "serve/prefill_wait",
                              "serve/first_sample"}

    dispatches = [e for e in spans if e["name"] == "serve/decode_dispatch"]
    collects = [e for e in spans if e["name"] == "serve/decode_collect"]
    chunks = [e for e in spans if e["name"] == "serve/prefill_chunk"]
    host_bound = [e for e in spans if e["name"] == "serve/host_bound"]
    assert host_bound and any(d["ahead"] for d in dispatches)
    for h in host_bound:
        end = h["t"] + h["dur"]
        # at most one launch starts while the host holds the device, and
        # the stretch ends inside it, once its device work is enqueued ...
        launches = [d for d in dispatches + chunks if h["t"] < d["t"] < end]
        assert len(launches) <= 1, (h, launches)
        for d in launches:
            assert end <= d["t"] + d["dur"], (h, d)
        # ... and every tick dispatched before it had been collected
        for d in dispatches:
            if d["t"] < h["t"]:
                assert any(c["tick"] == d["tick"] and c["t"] <= h["t"]
                           for c in collects), (d, h)


def test_host_bound_covers_host_work_before_a_launch(monkeypatch):
    """Host work a dispatch does before the device has work (here a slow
    ``_sync_tables``) is device idle the host causes: ``serve/host_bound``
    stays open over it and closes only once the launch is enqueued."""
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    delay = 0.02
    slow = []  # (start, end) of each delayed table sync
    sync = Engine._sync_tables

    def slow_sync(self, extra=None):
        t = time.perf_counter()
        time.sleep(delay)
        slow.append((t, time.perf_counter()))
        return sync(self, extra)

    monkeypatch.setattr(Engine, "_sync_tables", slow_sync)
    eng = Engine(model, params, slots=2, max_len=96, block_size=8,
                 prefill_chunk=8, obs=obs)
    eng.submit([3, 1, 4, 1, 5], max_tokens=6)
    eng.submit([2, 7, 1], max_tokens=6)
    eng.run()  # synchronous ticks: every decode dispatch follows a sync
    host_bound = _spans(obs, "serve/host_bound")
    dispatches = _spans(obs, "serve/decode_dispatch")
    assert dispatches and host_bound
    for d in dispatches:
        inside = [(a, b) for a, b in slow if d["t"] <= a < d["t"] + d["dur"]]
        assert inside, d
        for a, b in inside:
            assert any(h["t"] <= a and b <= h["t"] + h["dur"]
                       for h in host_bound), (d, a, b)


def test_pump_idle_covers_a_drain_then_submit_gap():
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    gap = 0.05

    async def scenario():
        fe = AsyncEngine(model, params, slots=2, max_len=96, block_size=8,
                         prefill_chunk=8, obs=obs)
        await _serve(fe, [[1, 2, 3]], 4)
        await asyncio.sleep(gap)
        await _serve(fe, [[4, 5, 6]], 4)

    asyncio.run(scenario())
    idle, = _spans(obs, "serve/pump_idle")
    submits = [e for e in obs.trace.events if e["ev"] == "submit"]
    finishes = [e for e in obs.trace.events if e["ev"] == "finish"]
    end = idle["t"] + idle["dur"]
    assert idle["t"] >= finishes[0]["t"]
    assert submits[1]["t"] <= end
    assert idle["dur"] >= gap
    # the restarted pump's first span begins after the idle stretch ends
    later = [e for e in _spans(obs) if e["name"] not in OVERLAYS and e["t"] > idle["t"]]
    assert min(e["t"] for e in later) >= end


def test_recompile_names_its_function_and_span():
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    # a geometry no other test serves: the decode program compiles anew
    eng = Engine(model, params, slots=7, max_len=72, block_size=8,
                 prefill_chunk=8, obs=obs)
    eng.submit([6, 2, 8], max_tokens=3)
    eng.run()
    compiles = [e for e in obs.trace.events if e["ev"] == "compile"]
    assert validate_events(obs.trace.events) == []
    by_sid = {e["sid"]: e for e in _spans(obs)}
    decode = [e for e in compiles if e["fun"] == "_decode" and e["stage"] == "compile"]
    assert decode, sorted({e["fun"] for e in compiles})
    assert all(by_sid[e["sid"]]["name"] == "serve/decode_dispatch" for e in decode)
    prefill = [e for e in compiles if e["fun"] == "_prefill" and e["stage"] == "compile"]
    assert all(by_sid[e["sid"]]["name"] == "serve/prefill_chunk" for e in prefill)
    assert obs.registry.get("serve_compiles_total", fun="_decode").value >= 1


@pytest.mark.parametrize("backend,tile_rows", [("paged", 2), ("ring", 4)])
def test_prefill_chunk_counts_tile_and_real_rows(backend, tile_rows):
    """``serve/prefill_chunk`` spans and ``prefill_chunk`` events carry the
    tile's rows and the rows with tokens in that chunk, and the two
    registry counters add them up."""
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    eng = Engine(model, params, slots=4, max_len=96, block_size=8,
                 prefill_chunk=8, prefill_batch=2, backend=backend, obs=obs)
    eng.submit(list(range(1, 6)), max_tokens=2)    # 5 tokens: chunk 0
    eng.submit(list(range(1, 20)), max_tokens=2)   # 19 tokens: chunks 0-2
    eng.run()
    assert validate_events(obs.trace.events) == []
    events = obs.trace.by_type("prefill_chunk")
    spans = _spans(obs, "serve/prefill_chunk")
    for recs in (events, spans):
        assert [(e["chunk"], e["tile_rows"], e["real_rows"]) for e in recs] \
            == [(0, tile_rows, 2), (1, tile_rows, 1), (2, tile_rows, 1)]
    reg = obs.registry
    assert reg.get("serve_prefill_tile_rows_total").value == 3 * tile_rows
    assert reg.get("serve_prefill_real_rows_total").value == 4
