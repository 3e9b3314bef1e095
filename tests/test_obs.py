"""repro.obs: registry math, trace schema/ordering invariants, overhead
contract, and the dispatch counters (DESIGN.md §9).

The serving-side tests replay the seeded schedules from
``test_serve_fuzz.py`` through an obs-enabled engine and assert the trace
tells a causally consistent story (submit ≤ admit ≤ first token ≤ finish,
preemptions bracketed by re-admissions) and that the TTFT histogram agrees
with the raw per-request stamps to one bucket width; the overhead guard
pins the disabled path to bitwise-identical tokens, identical tick counts,
and zero additional device syncs.
"""
import bisect
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_serve_fuzz as fuzz

from repro.kernels import dispatch
from repro.obs import (
    Histogram,
    MetricsRegistry,
    ObsConfig,
    Observer,
    bench_summary,
    default_observer,
    exp_buckets,
    prometheus_text,
    read_jsonl,
    reset_default_observer,
    resolve_observer,
    validate_events,
    validate_jsonl,
)
from repro.serve.engine import Engine


# ---------------------------------------------------------------------------
# Histogram / registry math
# ---------------------------------------------------------------------------
def test_histogram_percentiles_exact_to_bucket():
    h = Histogram(boundaries=[1.0, 2.0, 4.0, 8.0])
    for v in [0.5, 1.5, 1.5, 3.0, 3.5, 5.0, 6.0, 7.0, 7.5, 100.0]:
        h.observe(v)
    assert h.count == 10 and h.vmin == 0.5 and h.vmax == 100.0
    assert h.mean() == pytest.approx(sum([0.5, 1.5, 1.5, 3.0, 3.5, 5.0,
                                          6.0, 7.0, 7.5, 100.0]) / 10)
    # rank-q observation's bucket upper edge (overflow bucket -> vmax)
    assert h.percentile(0.0) == 1.0    # rank 1 = 0.5, bucket (0, 1]
    assert h.percentile(0.5) == 4.0    # rank 5 = 3.5, bucket (2, 4]
    assert h.percentile(0.9) == 8.0    # rank 9 = 7.5, bucket (4, 8]
    assert h.percentile(1.0) == 100.0  # overflow bucket reports observed max
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_histogram_single_bucket_reports_seen_value():
    h = Histogram(boundaries=[10.0])
    h.observe(2.0)
    # clamped to the observed max, not the (far) bucket edge
    assert h.percentile(0.5) == 2.0


def test_histogram_empty_edges():
    h = Histogram(boundaries=[1.0, 2.0])
    assert h.count == 0
    assert h.percentile(0.5) is None
    assert h.percentile(0.99) is None
    assert h.mean() is None
    other = Histogram(boundaries=[1.0, 2.0])
    h.merge(other)  # merging two empties stays empty
    assert h.count == 0 and h.percentile(0.5) is None


def test_histogram_merge_matches_combined_stream():
    rng = np.random.default_rng(0)
    a_vals = rng.exponential(0.01, 200)
    b_vals = rng.exponential(0.1, 100)
    a, b, both = (Histogram() for _ in range(3))
    for v in a_vals:
        a.observe(v)
        both.observe(v)
    for v in b_vals:
        b.observe(v)
        both.observe(v)
    a.merge(b)
    assert a.counts == both.counts
    assert a.count == both.count and a.sum == pytest.approx(both.sum)
    assert a.vmin == both.vmin and a.vmax == both.vmax
    for q in (0.5, 0.95, 0.99):
        assert a.percentile(q) == both.percentile(q)
    with pytest.raises(ValueError):
        a.merge(Histogram(boundaries=[1.0, 2.0]))


def test_histogram_bad_buckets():
    for bad in ([], [2.0, 1.0], [1.0, 1.0]):
        with pytest.raises(ValueError):
            Histogram(boundaries=bad)
    with pytest.raises(ValueError):
        exp_buckets(0.0, 2.0, 4)
    b = exp_buckets(1e-3, 2.0, 4)
    assert b == (1e-3, 2e-3, 4e-3, 8e-3)


def test_registry_kinds_labels_merge():
    reg = MetricsRegistry()
    reg.counter("reqs", family="dense").inc()
    reg.counter("reqs", family="dense").inc(2)
    reg.counter("reqs", family="moe").inc()
    assert reg.get("reqs", family="dense").value == 3
    assert reg.get("reqs", family="moe").value == 1
    assert reg.get("reqs", family="rwkv") is None
    reg.gauge("util").set(0.5)
    reg.histogram("lat").observe(1e-3)
    with pytest.raises(ValueError):  # name pinned to its first kind
        reg.gauge("reqs", family="dense")
    with pytest.raises(ValueError):
        reg.histogram("reqs")  # ...even with a fresh label set
    assert reg.counter("reqs").value == 0  # same kind, new labels: fine
    other = MetricsRegistry()
    other.counter("reqs", family="dense").inc(10)
    other.gauge("util").set(0.9)
    other.histogram("lat").observe(2e-3)
    reg.merge(other)
    assert reg.get("reqs", family="dense").value == 13
    assert reg.get("util").value == 0.9
    assert reg.get("lat").count == 2
    reg.reset()
    assert reg.get("util") is None


def test_prometheus_text_and_bench_summary():
    reg = MetricsRegistry()
    reg.counter("serve_tokens_total").inc(5)
    reg.gauge("serve_pool_utilization").set(0.75)
    h = reg.histogram("serve_ttft_seconds", buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(3.0)
    text = prometheus_text(reg)
    assert "# TYPE serve_tokens_total counter" in text
    assert "serve_tokens_total 5.0" in text
    assert "serve_pool_utilization 0.75" in text
    assert 'serve_ttft_seconds_bucket{le="0.1"} 1' in text
    assert 'serve_ttft_seconds_bucket{le="1.0"} 2' in text
    assert 'serve_ttft_seconds_bucket{le="+Inf"} 3' in text
    assert "serve_ttft_seconds_count 3" in text
    summ = bench_summary(reg)
    row = summ["serve_ttft_seconds"][0]
    assert row["count"] == 3 and row["p50"] == 1.0 and row["max"] == 3.0
    assert summ["serve_pool_utilization"][0]["value"] == 0.75


# ---------------------------------------------------------------------------
# Event schema validation
# ---------------------------------------------------------------------------
def test_validate_events_catches_malformed():
    good = [
        {"ev": "submit", "t": 1.0, "seq": 0, "rid": 0, "prompt_len": 3,
         "max_tokens": 4},
        {"ev": "finish", "t": 2.0, "seq": 1, "rid": 0, "tick": 5,
         "reason": "eos", "n_out": 2},
    ]
    assert validate_events(good) == []
    assert validate_events([{"ev": "nope", "t": 1.0, "seq": 0}])
    missing = [{"ev": "submit", "t": 1.0, "seq": 0, "rid": 0}]
    errs = validate_events(missing)
    assert any("missing field" in e for e in errs)
    wrong_type = [dict(good[0], rid="zero")]
    assert any("rid" in e for e in validate_events(wrong_type))
    bad_seq = [dict(good[0], seq=5), dict(good[1], seq=1)]
    assert any("seq" in e for e in validate_events(bad_seq))
    bool_rid = [dict(good[0], rid=True)]  # bool must not pass as int
    assert validate_events(bool_rid)
    inf_t = [dict(good[0], t=float("inf"))]
    assert any("non-finite" in e for e in validate_events(inf_t))


def test_validate_jsonl_bad_file(tmp_path):
    p = tmp_path / "trace.jsonl"
    assert validate_jsonl(p)  # missing file is an error
    p.write_text("")
    assert validate_jsonl(p) == [f"{p}: no events"]
    p.write_text('{"ev": "submit"\n')
    assert validate_jsonl(p)


# ---------------------------------------------------------------------------
# Observer resolution / env config
# ---------------------------------------------------------------------------
def test_resolve_observer_and_env(monkeypatch, tmp_path):
    assert resolve_observer(False) is None
    obs = Observer()
    assert resolve_observer(obs) is obs
    assert resolve_observer(ObsConfig(enabled=False)) is None
    assert isinstance(resolve_observer(ObsConfig()), Observer)
    with pytest.raises(TypeError):
        resolve_observer("yes")
    try:
        monkeypatch.delenv("REPRO_OBS", raising=False)
        reset_default_observer()
        assert default_observer() is None
        assert resolve_observer(None) is None
        monkeypatch.setenv("REPRO_OBS", "1")
        monkeypatch.setenv("REPRO_OBS_JSONL", str(tmp_path / "t.jsonl"))
        monkeypatch.setenv("REPRO_OBS_POOL_EVERY", "3")
        reset_default_observer()
        d = default_observer()
        assert d is not None and default_observer() is d  # memoized
        assert d.config.jsonl_path == str(tmp_path / "t.jsonl")
        assert d.config.pool_sample_every == 3
        assert resolve_observer(None) is d
    finally:
        reset_default_observer()  # next default_observer() re-reads real env


# ---------------------------------------------------------------------------
# Fuzz-schedule replay: trace ordering invariants + TTFT histogram agreement
# ---------------------------------------------------------------------------
def _replay(family, seed, tmp_path):
    """Drive one fuzz schedule through an obs-enabled engine."""
    model, params, _ = fuzz._setup(family)
    cfg = model.cfg
    rng, sched = fuzz._schedule(seed)
    slots = int(rng.integers(1, 4))
    kw = dict(slots=slots, max_len=fuzz.MAX_LEN, block_size=8,
              prefill_batch=2, prefill_chunk=8)
    obs = Observer(ObsConfig(enabled=True,
                             jsonl_path=str(tmp_path / "trace.jsonl")))
    eng = Engine(model, params, obs=obs, **kw)
    handles = fuzz._drive(eng, sched, cfg, family)
    obs.close()
    return eng, obs, handles


@pytest.mark.parametrize("family,seed", [("dense", 0), ("dense", 3),
                                         ("rwkv", 51)])
def test_trace_ordering_invariants(family, seed, tmp_path):
    eng, obs, handles = _replay(family, seed, tmp_path)
    events = obs.trace.events
    assert validate_events(events) == []
    # the JSONL on disk is the same stream, schema-valid
    disk = read_jsonl(tmp_path / "trace.jsonl")
    assert validate_jsonl(tmp_path / "trace.jsonl") == []
    assert [e["seq"] for e in disk] == [e["seq"] for e in events]

    by_rid: dict[int, dict[str, list]] = {}
    for e in events:
        if "rid" in e:
            by_rid.setdefault(e["rid"], {}).setdefault(e["ev"], []).append(e)
    assert set(by_rid) == {h.rid for h in handles}
    for h in handles:
        evs = by_rid[h.rid]
        submit, = evs["submit"]
        admits = evs["admit"]
        first, = evs["first_token"]
        finish, = evs["finish"]
        # submit <= first admit <= first token <= finish
        assert submit["t"] <= admits[0]["t"] <= first["t"] <= finish["t"]
        assert finish["n_out"] == len(h.out_tokens)
        assert finish["reason"] in ("eos", "max_tokens", "max_len")
        assert first["ttft_s"] == pytest.approx(h.t_first - h.t_submit)
        # every preempt is bracketed by a later re-admission
        for p in evs.get("preempt", []):
            assert any(a["t"] >= p["t"] for a in admits), \
                f"rid {h.rid}: preempt at {p['t']} never re-admitted"
        # re-admissions only ever follow a preemption
        assert len(admits) == 1 + len(evs.get("preempt", []))
    # decode ticks count active slots truthfully
    for e in events:
        if e["ev"] == "decode_tick":
            assert 1 <= e["active"] <= eng.slots
        if e["ev"] == "pool_sample":
            assert 0.0 <= e["utilization"] <= 1.0


def test_preemption_trace_bracketing():
    """A deliberately tight pool must preempt, and the trace must show every
    preempted request re-admitted and finished."""
    model, params, _ = fuzz._setup("dense")
    # two slots, 7 usable blocks of 4: both sequences admit at 3 blocks
    # (prompt 8 + lookahead) but grow to 4 while decoding — 8 > 7 preempts
    obs = Observer()
    eng = Engine(model, params, slots=2, max_len=96, block_size=4,
                 num_blocks=8, prefill_batch=2, prefill_chunk=8, obs=obs)
    handles = [eng.submit(list(range(1, 9)), max_tokens=6) for _ in range(3)]
    eng.run()
    assert all(h.done for h in handles)
    events = obs.trace.events
    assert validate_events(events) == []
    preempts = [e for e in events if e["ev"] == "preempt"]
    assert preempts, "tight pool never preempted — test geometry is stale"
    assert eng.obs.registry.get("serve_preemptions_total").value == len(preempts)
    admits = [e for e in events if e["ev"] == "admit"]
    finishes = {e["rid"] for e in events if e["ev"] == "finish"}
    for p in preempts:
        assert any(a["rid"] == p["rid"] and a["seq"] > p["seq"] for a in admits)
        assert p["rid"] in finishes


def test_ttft_histogram_matches_raw_stamps(tmp_path):
    """Acceptance: histogram percentiles agree with the raw per-request
    ``t_first - t_submit`` values to one bucket width."""
    raw = []
    hist = None
    for seed in (1, 2, 4):
        eng, obs, handles = _replay("dense", seed, tmp_path / str(seed))
        raw.extend(h.t_first - h.t_submit for h in handles)
        h = obs.registry.get("serve_ttft_seconds")
        if hist is None:
            hist = h
        else:
            hist.merge(h)
    assert hist.count == len(raw)
    bounds = hist.boundaries
    raw.sort()
    for q in (0.5, 0.95, 0.99):
        rank_val = raw[max(0, int(np.ceil(q * len(raw))) - 1)]
        hp = hist.percentile(q)
        i = bisect.bisect_left(bounds, rank_val)
        lo = bounds[i - 1] if i else 0.0
        hi = bounds[i] if i < len(bounds) else float("inf")
        assert lo < hp <= hi or hp == rank_val, \
            f"p{q}: hist {hp} not within one bucket of raw {rank_val}"


# ---------------------------------------------------------------------------
# Overhead contract: obs disabled == bitwise-identical behavior, no syncs
# ---------------------------------------------------------------------------
def test_disabled_obs_identical_tokens_ticks_and_syncs(monkeypatch):
    model, params, _ = fuzz._setup("dense")
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12, 13, 14]]

    def run(obs):
        syncs = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: (syncs.append(1), real(x))[1])
        eng = Engine(model, params, slots=2, max_len=96, block_size=8,
                     prefill_batch=2, prefill_chunk=8, obs=obs)
        reqs = [eng.submit(p, max_tokens=5) for p in prompts]
        eng.run()
        monkeypatch.setattr(jax, "block_until_ready", real)
        return [r.out_tokens for r in reqs], eng._tick_no, len(syncs)

    toks_off, ticks_off, syncs_off = run(False)
    toks_on, ticks_on, syncs_on = run(Observer())
    assert toks_on == toks_off  # bitwise-identical schedule + tokens
    assert ticks_on == ticks_off
    # enabling obs must not add device syncs; disabling it certainly must not
    assert syncs_on == syncs_off


# ---------------------------------------------------------------------------
# kernels.dispatch counters, resolved_backend
# ---------------------------------------------------------------------------
def test_dispatch_counts_and_resolved_backend():
    dispatch.reset_dispatch_metrics()
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    dispatch.dense_linear(x, w, role="mlp_up")
    dispatch.dense_linear(x, w, role="mlp_up")
    dispatch.dense_linear(x, w)  # falls back to the kind label
    counts = dispatch.dispatch_counts()
    assert counts[("mlp_up", "xla")] == 2
    assert counts[("dense", "xla")] == 1
    assert dispatch.resolved_backend("mlp_up") == "xla"
    assert dispatch.resolved_backend("never_dispatched") is None
    # trace-time semantics: a jitted program counts once per trace, and the
    # baked-in backend is what resolved_backend reports afterwards
    dispatch.reset_dispatch_metrics()
    f = jax.jit(lambda a: dispatch.dense_linear(a, w, role="probe"))
    f(x)
    f(x)
    f(x)  # cached executions re-run nothing at trace level
    assert dispatch.dispatch_counts()[("probe", "xla")] == 1


def test_engine_records_prefill_dispatch():
    """The engine's jitted steps surface which attention backend actually
    traced — the benchmark reads this instead of self-reporting."""
    model, params, _ = fuzz._setup("dense")
    dispatch.reset_dispatch_metrics()
    eng = Engine(model, params, slots=2, max_len=96, block_size=8,
                 kernel_backend="ref")
    req = eng.submit([1, 2, 3], max_tokens=3)
    eng.run()
    assert req.done
    # steps are memoized across engines, so the trace may have happened in an
    # earlier test of this process — but with reset_dispatch_metrics() above,
    # a fresh count here proves this engine's programs re-used or re-traced
    # through the dispatcher; at minimum the resolved backend is queryable
    rb = dispatch.resolved_backend("attn_prefill")
    assert rb in (None, "ref", "pallas-interpret", "pallas")


# ---------------------------------------------------------------------------
# Trainer metrics ride the same registry
# ---------------------------------------------------------------------------
def test_trainer_metrics(tmp_path, key):
    from repro.config import TrainConfig
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.models import build_model
    from repro.train.step import build_train_step, init_train_state
    from repro.train.trainer import Trainer

    cfg = get_config("tinyllama-1.1b", reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    tc = TrainConfig(global_batch=2, seq_len=16, lr=3e-3, warmup_steps=2,
                     total_steps=6, optimizer="adamw", remat="none")
    state = init_train_state(model, tc, key)
    step = jax.jit(build_train_step(model, tc))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2,
                    seed=0)
    obs = Observer()
    tr = Trainer(step, state, dc, obs=obs)
    rep = tr.run(4, log_every=0)
    assert rep.steps_done == 4
    h = obs.registry.get("train_step_seconds")
    assert h.count == 4
    # bucket-resolution agreement with the report's own perf_counter stamps
    assert h.vmax == pytest.approx(max(rep.step_times))
    assert obs.registry.get("train_steps_total").value == 4
    assert obs.registry.get("train_tokens_per_second").value > 0
    # JSON round-trip of the summary (what BENCH files embed)
    json.dumps(bench_summary(obs.registry))


# ---------------------------------------------------------------------------
# Cancellation / deadline events: schema + causal ordering + slot reuse
# ---------------------------------------------------------------------------
def test_cancel_trace_ordering_and_slot_reuse():
    """submit <= admit <= cancel in the trace, the cancel names the freed
    slot, and a later admit reuses that slot after the cancel's seq."""
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    eng = Engine(model, params, slots=1, max_len=96, block_size=8,
                 prefill_chunk=8, obs=obs)
    victim = eng.submit([1, 2, 3], max_tokens=40)
    waiter = eng.submit([4, 5, 6], max_tokens=4)
    for _ in range(3):
        eng.tick()
    assert eng.cancel(victim)
    eng.run()
    assert waiter.done and not waiter.cancelled
    events = obs.trace.events
    assert validate_events(events) == []
    by = {e["ev"]: e for e in events if e.get("rid") == victim.rid}
    assert by["submit"]["seq"] <= by["admit"]["seq"] <= by["cancel"]["seq"]
    assert by["submit"]["t"] <= by["admit"]["t"] <= by["cancel"]["t"]
    assert by["cancel"]["slot"] == by["admit"]["slot"] == 0
    assert by["cancel"]["reason"] == "user"
    assert "finish" not in by  # a cancel is terminal, never double-finished
    waiter_admit, = [e for e in events if e["ev"] == "admit"
                     and e["rid"] == waiter.rid]
    assert waiter_admit["slot"] == 0  # the cancelled request's slot, reused
    assert waiter_admit["seq"] > by["cancel"]["seq"]
    assert obs.registry.get("serve_cancellations_total").value == 1


def test_cancel_queued_and_deadline_events_validate():
    model, params, _ = fuzz._setup("dense")
    obs = Observer()
    eng = Engine(model, params, slots=1, max_len=96, block_size=8,
                 prefill_chunk=8, obs=obs)
    active = eng.submit([1, 2, 3], max_tokens=4)
    queued = eng.submit([4, 5, 6], max_tokens=4)
    doomed = eng.submit([7, 8, 9], max_tokens=4, deadline_s=1e-9)
    eng.tick()
    assert eng.cancel(queued)
    eng.run()
    assert active.done and not active.cancelled
    events = obs.trace.events
    assert validate_events(events) == []
    cancel_q, = [e for e in events if e["ev"] == "cancel"
                 and e["rid"] == queued.rid]
    assert cancel_q["slot"] == -1  # cancelled before ever holding a slot
    miss, = [e for e in events if e["ev"] == "deadline_miss"]
    assert miss["rid"] == doomed.rid and miss["deadline_s"] == 1e-9
    cancel_d, = [e for e in events if e["ev"] == "cancel"
                 and e["rid"] == doomed.rid]
    assert cancel_d["reason"] == "deadline" and cancel_d["seq"] > miss["seq"]
    assert obs.registry.get("serve_deadline_miss_total").value == 1
    assert obs.registry.get("serve_cancellations_total").value == 2
