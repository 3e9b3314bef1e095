"""repro.obs — observability for the serving/training stack (DESIGN.md §9).

One :class:`Observer` bundles the three layers:

* a :class:`~repro.obs.registry.MetricsRegistry` (counters / gauges /
  mergeable fixed-bucket histograms with exact-to-one-bucket percentiles),
* a :class:`~repro.obs.trace.Trace` of structured scheduler events
  (monotonic timestamps, optionally streamed to JSONL),
* spans (:meth:`Observer.span`): timed regions of the serving loop, each a
  ``span`` event in that trace and a ``jax.profiler.TraceAnnotation``
  ``<name>#<sid>`` in a profiler trace, and ``compile`` events with the
  ``serve_compiles_total{fun}`` counter for every program JAX builds.

**Overhead contract:** everything is off by default.  Components take an
``obs=None`` argument: ``None`` resolves to the process-default observer
built from the environment (``REPRO_OBS`` unset → *no* observer — the
disabled hot path is a single ``is None`` check, no allocation, no device
syncs), ``False`` forces off, and an :class:`Observer` / enabled
:class:`ObsConfig` turns instrumentation on explicitly.  Enabling obs adds
host-side bookkeeping only; it never inserts a device sync the engine was
not already doing (TTFT was always stamped after ``block_until_ready``).

Env knobs (read once, at first ``default_observer()`` call):

====================================  =======================================
``REPRO_OBS=1``                       enable the process-default observer
``REPRO_OBS_JSONL=<path>``            stream trace events to ``<path>``
``REPRO_OBS_POOL_EVERY=<n>``          sample pool gauges every n ticks (1)
====================================  =======================================
"""
from __future__ import annotations

import os
import time
import weakref
from dataclasses import dataclass

from .export import (  # noqa: F401  (public re-exports)
    JsonlWriter,
    bench_summary,
    prometheus_text,
    read_jsonl,
    validate_events,
    validate_jsonl,
)
from .registry import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    exp_buckets,
)
from .trace import Span, Trace  # noqa: F401

ENV_ENABLE = "REPRO_OBS"
ENV_JSONL = "REPRO_OBS_JSONL"
ENV_POOL_EVERY = "REPRO_OBS_POOL_EVERY"


def _truthy(v: str | None) -> bool:
    return (v or "").strip().lower() not in ("", "0", "false", "no", "off")


@dataclass(frozen=True)
class ObsConfig:
    """What to record.  ``enabled=False`` means "no observer at all"."""

    enabled: bool = True
    jsonl_path: str | None = None      # stream trace events here
    pool_sample_every: int = 1          # ticks between pool gauge samples

    @classmethod
    def from_env(cls) -> "ObsConfig":
        return cls(
            enabled=_truthy(os.environ.get(ENV_ENABLE)),
            jsonl_path=os.environ.get(ENV_JSONL) or None,
            pool_sample_every=max(1, int(os.environ.get(ENV_POOL_EVERY, "1"))),
        )


# jax.monitoring events of building a program, by the stage they time
_COMPILE_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                   "/jax/core/compile/backend_compile_duration": "compile"}
_LIVE: weakref.WeakSet = weakref.WeakSet()  # observers that record compiles
_LISTENING: list = []  # the listener, once registered (one per process)


def _on_compile(event: str, duration: float, **kwargs) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    fun = str(kwargs.get("fun_name", "?"))
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]  # the backend stage names ``jit(<fun>)``
    for obs in list(_LIVE):
        obs._compiled(fun, stage, duration)


class Observer:
    """Live instrumentation handle: registry + trace + spans.

    Every live observer records each program JAX traces or compiles in the
    process: a ``compile`` event naming the function, its stage and the
    span open at the time, and, per executable built or loaded,
    ``serve_compiles_total{fun}``.  After warm-up that counter should not
    move; a rise names the function that recompiled.
    """

    def __init__(self, config: ObsConfig | None = None, *,
                 registry: MetricsRegistry | None = None):
        self.config = config if config is not None else ObsConfig()
        self.registry = registry if registry is not None else MetricsRegistry()
        writer = (JsonlWriter(self.config.jsonl_path)
                  if self.config.jsonl_path else None)
        self.trace = Trace(writer=writer)
        self._stack: list[Span] = []  # open nested spans (one loop thread)
        if not _LISTENING:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _LISTENING.append(_on_compile)
        _LIVE.add(self)

    def event(self, ev: str, t: float | None = None, **fields) -> dict:
        return self.trace.emit(ev, t=t, **fields)

    def span(self, name: str, **fields) -> Span:
        """A nested span for a ``with`` block: ``with obs.span("serve/x"):``."""
        return Span(self.trace, self._stack, name, fields)

    def overlay(self, name: str, **fields) -> Span:
        """An overlay span, open from now until its ``close()``; it need not
        nest with the spans opened meanwhile."""
        return Span(self.trace, None, name, fields).open()

    def _compiled(self, fun: str, stage: str, dur: float) -> None:
        if stage == "compile":
            self.registry.counter("serve_compiles_total", fun=fun).inc()
        self.trace.emit("compile", t=time.perf_counter() - dur, fun=fun,
                        stage=stage, dur=dur,
                        sid=self._stack[-1].sid if self._stack else -1)

    def close(self) -> None:
        self.trace.close()


_DEFAULT: list = []  # memo cell: [] = unresolved, [None | Observer] = resolved


def default_observer() -> Observer | None:
    """Process-default observer from the environment, memoized.

    ``None`` unless ``REPRO_OBS`` is truthy — the disabled path must cost
    one ``is None`` check at the call sites.
    """
    if not _DEFAULT:
        cfg = ObsConfig.from_env()
        _DEFAULT.append(Observer(cfg) if cfg.enabled else None)
    return _DEFAULT[0]


def reset_default_observer() -> None:
    """Drop the memoized default (tests re-read the environment)."""
    if _DEFAULT and _DEFAULT[0] is not None:
        _DEFAULT[0].close()
    _DEFAULT.clear()


def resolve_observer(obs) -> Observer | None:
    """Normalize a component's ``obs`` argument.

    ``None`` → the env-driven process default; ``False`` → force-off;
    an :class:`Observer` passes through; an :class:`ObsConfig` builds a
    fresh observer (or ``None`` when ``enabled=False``).
    """
    if obs is None:
        return default_observer()
    if obs is False:
        return None
    if isinstance(obs, Observer):
        return obs
    if isinstance(obs, ObsConfig):
        return Observer(obs) if obs.enabled else None
    raise TypeError(f"obs must be None, False, ObsConfig or Observer; "
                    f"got {type(obs).__name__}")
