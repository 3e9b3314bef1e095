"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file (with what it names beside it: the plain reference,
and where it has one its ``ops`` module), its traffic mix
``bench/traffic/<traffic>.json``, its correctness limit
``bench/limits/<cell>.json`` and one reader per quantity,
``bench/metrics/<quantity>.py``: a metric named ``<quantity>`` or
``<quantity>.<suffix>`` (the same quantity, split by the end-to-end metric it
moves) is read by that file.  Unit, layer and what a metric moves are stated
in ``BENCHMARK.json`` alone.  What is particular to a model (its keys and
TT roles, its weight rules, its operation counts) is read from those files
(``model.py``); the session backend is the program's default for the
model's family, and the attention roles that have to run on the Pallas
kernels follow from it (``ATTENTION_ROLES``).  So adding a cell, a
configuration of a served family, or a metric adds files and entries and
edits none.
"""
from __future__ import annotations

import asyncio
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

import check
import model as bench_model
import opcount
import serve as drivers
import traffic as gen
from peaks import peaks_for

WAIT_S = 60.0       # how long past the close counted requests are waited for
TAIL_S = 120.0      # open-loop arrivals scheduled past the close


@dataclass
class Run:
    """What a metric reader may read about one run."""

    cell: dict
    cj: dict
    mix: dict
    tt: dict
    seconds: float
    setup_s: float
    records: list = field(default_factory=list)
    t0: float = 0.0
    t1: float = 0.0
    events: list | None = None      # program's scheduler events (traced run)
    trace: dict | None = None       # reduced device trace (traced run)
    peaks: dict | None = None
    ops: object = opcount           # the configuration's ops module

    @property
    def model(self) -> dict:
        return self.cj["model"]


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """Entries of ``spec[kind]`` this cell reports: those listing it, or,
    without a ``workloads`` key, every cell (an end-to-end metric) or every
    cell that reports what it moves (a per-layer metric)."""
    e2e = {m["name"] for m in cell_metrics(spec, cell, "end_to_end")} \
        if kind == "per_layer" else set()
    return [m for m in spec[kind]
            if (cell in m["workloads"] if "workloads" in m
                else kind == "end_to_end" or m["moves"] in e2e)]


def reader(entry: dict, root: Path):
    """The reader module of a metric: the file of its quantity, the part of
    its name before the first dot."""
    return bench_model.load_module(
        root / "bench" / "metrics" / f"{entry['name'].split('.')[0]}.py")


def dispatch_counts() -> dict:
    from repro.kernels import dispatch
    return dispatch.dispatch_counts()


_VERIFIED: dict = {}  # roles already checked in this process, per program
# kernel roles the attention of each session backend dispatches
ATTENTION_ROLES = {"paged": ("attn_paged", "attn_prefill")}


def check_roles(want: list[str], expect: str, before: dict, key) -> list[str]:
    """Roles that did not resolve to ``expect`` in the traces since
    ``before``: of ``want``, those that resolved elsewhere or never
    dispatched, and any other that resolved to neither ``expect`` nor XLA.
    The programs trace once per process, so a run that traced nothing new
    reuses the verdict of the run that did."""
    roles: dict[str, set[str]] = {}
    for (role, backend), n in dispatch_counts().items():
        if n > before.get((role, backend), 0) and backend != "xla":
            roles.setdefault(role, set()).add(backend)
    if not roles and key in _VERIFIED:
        return _VERIFIED[key]
    bad = sorted(r for r in want if roles.get(r) != {expect}) + \
        sorted(r for r, b in roles.items() if b != {expect} and r not in want)
    _VERIFIED[key] = bad
    return bad


class CompileCounter:
    """Counts executables compiled or loaded (JAX's backend-compile events)
    while ``active``; a context manager that unregisters itself."""

    def __init__(self):
        self.n = 0
        self.active = False

    def _on(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)


def device_info(chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache in ``<root>/.jax_cache``, keeping
    every program and evicting none, whatever the machine's defaults: so
    every run after a checkout's first loads all it runs from there."""
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()  # honours the JAX_COMPILATION_CACHE_DIR set by run.py
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float, trace: bool,
             *, root: Path, t_start: float, kernel_backend: str | None = None,
             expect_backend: str = "pallas", with_control: bool = False,
             traffic_dir: Path | None = None, limits_dir: Path | None = None,
             mix_override: dict | None = None, compare: bool = True,
             keep_records: dict | None = None) -> dict:
    """Set up, measure, check; returns the result object.

    ``kernel_backend``/``expect_backend`` pin and check the program's kernel
    backend (the chip runs ``auto``, which resolves to ``pallas``);
    ``with_control`` also reads the float8 control on the same sample and
    puts it in the program's place: ``control_correct`` in the result is the
    verdict on it;
    ``mix_override`` replaces keys of the traffic mix (the knee sweep's
    rates) and ``compare=False`` skips the check (``correct`` is then false);
    ``keep_records`` receives the client records and the window.
    """
    traffic_dir = traffic_dir or root / "bench" / "traffic"
    limits_dir = limits_dir or root / "bench" / "limits"
    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cpath = root / cfg_entry["file"]
    cj = bench_model.load_config(cpath)
    mix = dict(gen.load_mix(traffic_dir / f"{cell['traffic']}.json"),
               **(mix_override or {}))
    e2e = cell_metrics(spec, cell_name, "end_to_end")
    layer = cell_metrics(spec, cell_name, "per_layer") if trace else []
    readers = {m["name"]: reader(m, root) for m in e2e + layer}
    dev_kind = jax.devices()[0].device_kind
    peaks = peaks_for(dev_kind) if trace else None
    with CompileCounter() as counter:
        return _run(cell, cpath, cj, mix, e2e, layer, readers, peaks, seed,
                    seconds, trace, counter, t_start=t_start,
                    kernel_backend=kernel_backend, expect_backend=expect_backend,
                    with_control=with_control, limits_dir=limits_dir,
                    compare=compare, keep_records=keep_records)


def _run(cell, cpath, cj, mix, e2e, layer, readers, peaks, seed, seconds,
         trace, counter, *, t_start, kernel_backend, expect_backend,
         with_control, limits_dir, compare, keep_records):
    from repro.models import build_model
    from repro.obs import ObsConfig, Observer
    from repro.serve.frontend import AsyncEngine

    cell_name = cell["name"]
    eng_geo = mix["engine"]
    cfg = bench_model.served_config(cj, kernel_backend=kernel_backend)
    model = build_model(cfg)
    params = bench_model.make_params(
        model, cj, seed,
        leaf_rule=getattr(check.load_reference(cpath, cj), "leaf_rule", None))
    jax.block_until_ready(params)
    obs = Observer(ObsConfig(enabled=True)) if trace else False
    engine = drivers.RecordingEngine(
        model, params, slots=eng_geo["slots"], max_len=eng_geo["max_len"],
        num_blocks=eng_geo["num_blocks"],
        prefill_chunk=eng_geo["prefill_chunk"],
        prefill_batch=eng_geo["prefill_batch"],
        cache_dtype=eng_geo["cache_dtype"], kernel_backend=kernel_backend,
        obs=obs)
    front = AsyncEngine(engine=engine, dispatch_ahead=True)
    vocab = cj["model"]["vocab_size"]
    before = dispatch_counts()
    asyncio.run(drivers.warm_up(front, gen.warmup_requests(mix, vocab)))
    want = list(bench_model.tt_roles(cj)) + list(
        ATTENTION_ROLES.get(engine.session.backend, ()))
    bad_roles = check_roles(want, expect_backend, before,
                            (cfg, tuple(sorted(eng_geo.items())), kernel_backend))
    engine.clear_records()
    if trace:
        obs.trace.events.clear()

    run = Run(cell=cell, cj=cj, mix=mix, tt=bench_model.tt_roles(cj),
              ops=bench_model.ops_module(cpath, cj),
              seconds=seconds, setup_s=time.perf_counter() - t_start,
              peaks=peaks)
    tracer = _Tracer(seconds) if trace else None

    def on_window(t0, t1):
        counter.active = True
        if tracer is not None:
            tracer.schedule(t0)

    schedule = gen.open_schedule(mix, seed, seconds, vocab, TAIL_S)
    records, t0, t1 = asyncio.run(_with_tracer(tracer, drivers.open_loop(
        front, schedule, seconds, WAIT_S, on_window=on_window)))
    counter.active = False
    device = device_info(cell["chips"])
    run.records, run.t0, run.t1 = records, t0, t1
    if keep_records is not None:
        keep_records.update(records=records, t0=t0, t1=t1)
    if trace:
        run.events = list(obs.trace.events)
        run.trace = tracer.reduce(engine, eng_geo, run)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    late = [r.submitted - r.due for r in records if r.submitted]
    say(f"[window] {len(records)} requests, {sum(len(r.tokens) for r in records)} "
        f"tokens; compiles inside the window: {counter.n}")
    if late:
        say(f"[generator] late by p50 {1e3 * float(np.median(late)):.3f} ms, "
            f"max {1e3 * max(late):.3f} ms over {len(late)} requests")

    # the benchmark's records of the program's calls, which the traced
    # metrics read: a window that served tokens and recorded no prefill or
    # no decode call means the hooks in serve.RecordingEngine no longer sit
    # on the program's path
    served = sum(len(r.tokens) for r in records)
    hooks_silent = int(served > 0 and not (engine.prefills and engine.decodes))

    # free the program's state before the reference runs
    engine.state = None
    del front, engine
    gc.collect()

    metrics = {}
    for m in (layer if trace else e2e):
        v = readers[m["name"]].read(run)
        # a tail that a missing request made infinite is left out (the run
        # is not correct then); a listed metric that read nothing fails the
        # run below
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    failed = [r for r in records if r.error or not r.finished]
    checks = {"failed_requests": {"value": len(failed), "limit": 0},
              "roles_not_pallas": {"value": len(bad_roles), "limit": 0},
              "hooks_silent": {"value": hooks_silent, "limit": 0}}
    if trace:
        checks["per_layer_unread"] = {
            "value": sum(m["name"] not in metrics for m in layer), "limit": 0}
    control_checks = None
    if compare:
        sample = check.draw_sample(records, seed, mix["check"]["min_tokens"],
                                   mix["check"]["max_requests"])
        ref = check.reference(cpath, mix)
        control = check.reference(cpath, mix, quant="fp8") if with_control else None
        cmp = check.compare(params, ref, sample, control)
        limit = _limit(limits_dir, cell_name)
        checks["tokens_compared"] = {"value": cmp["tokens"], "limit": 1}
        checks["served_gap"] = {"value": cmp["served_gap"], "limit": limit}
        if control is not None:
            # the control in the program's place: its gap where the
            # program's stood, judged by the same verdict
            control_checks = dict(checks, served_gap={
                "value": cmp["control_gap"], "limit": limit})
            checks["control_gap"] = control_checks["served_gap"]
    if bad_roles:
        say(f"[kernels] roles not served by {expect_backend!r}: {bad_roles}")
    if hooks_silent:
        say("[hooks] the window served tokens but recorded no prefill or no "
            "decode call: the traced metrics cannot pair device time with work")

    result = {"correct": verdict(checks) if compare else False,
              "attempted": len(records), "failed": len(failed),
              "metrics": metrics, "device": device}
    if control_checks is not None:
        result["control_correct"] = verdict(control_checks)
    if trace:
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} {c['value']} limit {c['limit']}")
    return result


# checks whose number has to reach its limit; every other has to stay at or
# under it
AT_LEAST = {"tokens_compared"}


def verdict(checks: dict) -> bool:
    """``correct``: every number compared within its limit.  A number or a
    limit that is missing passes nothing."""
    for name, c in checks.items():
        if name == "control_gap":
            continue
        v, lim = c["value"], c["limit"]
        if v is None or lim is None:
            return False
        if (v < lim) if name in AT_LEAST else (v > lim):
            return False
    return True


def _limit(limits_dir: Path, cell_name: str) -> float | None:
    """The cell's limit on the widest gap; ``None`` (nothing passes) until
    one has been set from readings."""
    path = limits_dir / f"{cell_name}.json"
    if not path.exists():
        return None
    with open(path) as f:
        return float(json.load(f)["served_gap"]["limit"])


async def _with_tracer(tracer, coro):
    if tracer is None:
        return await coro
    task = asyncio.ensure_future(tracer.run())
    try:
        return await coro
    finally:
        await task


class _Tracer:
    """Profiles the middle third of the window (at most 10 s) in a run of
    its own; the reduction reads the trace once the window has closed."""

    def __init__(self, seconds: float):
        self.offset = seconds / 3.0
        self.length = min(10.0, seconds / 3.0)
        self.t0 = None
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def schedule(self, t0: float) -> None:
        self.t0 = t0

    async def run(self) -> None:
        while self.t0 is None:
            await asyncio.sleep(0.001)
        await asyncio.sleep(max(0.0, self.t0 + self.offset - time.perf_counter()))
        jax.profiler.start_trace(self.dir)
        self.started = time.perf_counter()
        await asyncio.sleep(self.length)
        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, engine, geo, run) -> dict:
        import devtrace as reduction
        try:
            return reduction.reduce_run(self.dir, engine, geo, run,
                                        (self.started, self.stopped))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
