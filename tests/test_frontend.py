"""Async front-end: token identity under load, cancel/deadline semantics.

The core acceptance test fuzzes the asyncio front-end with seeded Poisson
arrivals and random mid-stream cancellations, with dispatch-ahead both on
and off: every request that *completes* must emit tokens bitwise-identical
to generating it alone through ``model.prefill`` + ``model.decode_step``
(the same reference the synchronous scheduler fuzz pins), and every
cancelled request must hold a strict greedy prefix.  The satellites pin the
submit-time validation, drained-engine reuse, deadline expiry, and that
dispatch-ahead actually engages (``serve_ahead_ticks_total``).

Tests drive the event loop with ``asyncio.run`` inside ordinary sync test
functions — no asyncio pytest plugin required.
"""
import asyncio

import jax.numpy as jnp
import numpy as np
import pytest
import test_serve_fuzz as fuzz

from repro.obs import Observer
from repro.serve import AsyncEngine
from repro.serve.engine import Engine


def _ref(model, params, prompt, n, max_len=96):
    """Greedy one-request-at-a-time reference (any length)."""
    logits, cache = model.prefill(params,
                                  {"tokens": jnp.asarray([prompt], jnp.int32)},
                                  cache_dtype=jnp.float32, max_len=max_len)
    out = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(n - 1):
        logits, cache = model.decode_step(
            params, cache, {"tokens": jnp.asarray([[out[-1]]], jnp.int32)},
            jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0])))
        pos += 1
    return out


async def _play(frontend, schedule):
    """Submit per-Poisson-gap with consumers and cancel timers attached."""
    handles, tasks = [], []

    async def consume(h):
        async for _ in h.stream():
            pass

    async def cancel_later(h, delay):
        try:
            await asyncio.wait_for(h.wait_done(), timeout=delay)
        except asyncio.TimeoutError:
            h.cancel()

    for gap, prompt, max_tokens, eos, cancel_after in schedule:
        await asyncio.sleep(gap)
        h = frontend.submit(prompt, max_tokens=max_tokens, eos=eos)
        handles.append(h)
        tasks.append(asyncio.create_task(consume(h)))
        if cancel_after is not None:
            tasks.append(asyncio.create_task(cancel_later(h, cancel_after)))
    await frontend.drain()
    await asyncio.gather(*tasks)
    return handles


def _fuzz_schedule(reference, seed):
    """Poisson gaps, mixed lengths, reference-drawn eos, random cancels."""
    rng = np.random.default_rng(3000 + seed)
    schedule = []
    for _ in range(int(rng.integers(4, 8))):
        prompt = [int(t) for t in rng.integers(0, 256, int(rng.integers(1, 11)))]
        max_tokens = int(rng.integers(1, 7))
        eos = None
        if rng.random() < 0.3:
            cont = reference(prompt)
            eos = cont[int(rng.integers(0, len(cont)))]
        cancel_after = (float(rng.uniform(0.001, 0.02))
                        if rng.random() < 0.35 else None)
        schedule.append((float(rng.exponential(0.004)), prompt, max_tokens,
                         eos, cancel_after))
    kw = dict(slots=int(rng.integers(1, 4)), max_len=96, block_size=8,
              num_blocks=int(rng.integers(5, 20)), prefill_batch=2,
              prefill_chunk=8)
    return schedule, kw


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("dispatch_ahead", [True, False])
def test_async_token_identity_fuzz(seed, dispatch_ahead):
    """Completed requests match the solo reference bitwise; cancelled ones
    hold a strict greedy prefix — under Poisson arrivals + random cancels,
    with and without dispatch-ahead double buffering."""
    model, params, reference = fuzz._setup("dense")
    schedule, kw = _fuzz_schedule(reference, seed)
    frontend = AsyncEngine(model, params, dispatch_ahead=dispatch_ahead, **kw)
    handles = asyncio.run(_play(frontend, schedule))
    for h, (_, prompt, max_tokens, eos, _) in zip(handles, schedule):
        expected = fuzz._expected(reference, prompt, max_tokens, eos)
        if h.cancelled:
            assert h.finish_reason == "user"
            assert len(h.out_tokens) < len(expected)
            assert h.out_tokens == expected[:len(h.out_tokens)], \
                f"seed {seed}: cancelled rid {h.rid} diverged from reference"
        else:
            assert h.done
            assert h.out_tokens == expected, \
                f"seed {seed}: rid {h.rid} {h.out_tokens} != {expected}"


def test_dispatch_ahead_engages_and_matches_reference():
    """A long single-stream decode must run mostly ahead ticks and still be
    bitwise-identical to the solo reference."""
    model, params, _ = fuzz._setup("dense")
    prompt = [5, 3, 8, 1]
    n = 24
    expected = _ref(model, params, prompt, n)

    async def scenario():
        fe = AsyncEngine(model, params, slots=2, max_len=96, block_size=8,
                         prefill_chunk=8, obs=Observer())
        toks = [t async for t in fe.submit(prompt, max_tokens=n).stream()]
        await fe.drain()
        return toks, fe.engine.obs.registry

    toks, reg = asyncio.run(scenario())
    assert toks == expected
    ahead = reg.get("serve_ahead_ticks_total").value
    assert ahead > 0, "dispatch-ahead never engaged"
    assert ahead <= reg.get("serve_decode_ticks_total").value


def test_cancel_mid_stream_keeps_prefix_and_frees_slot():
    model, params, _ = fuzz._setup("dense")
    prompt = [2, 7, 1]
    expected = _ref(model, params, prompt, 30)

    async def scenario():
        fe = AsyncEngine(model, params, slots=1, max_len=96, block_size=8,
                         prefill_chunk=8)
        h = fe.submit(prompt, max_tokens=30)
        got = []
        async for tok in h.stream():
            got.append(tok)
            if len(got) == 3:
                h.cancel()
                h.cancel()  # idempotent
        await fe.drain()
        # the freed slot must serve a fresh request afterwards
        h2 = fe.submit(prompt, max_tokens=4)
        after = await h2.result()
        await fe.drain()
        return h, got, after, fe

    h, got, after, fe = asyncio.run(scenario())
    assert h.cancelled and h.finish_reason == "user"
    assert got == h.out_tokens
    assert 3 <= len(got) < 30  # cancel applies at the next safe point
    assert got == expected[:len(got)]
    assert after == expected[:4]
    assert fe.engine.manager.num_free == fe.engine.manager.num_blocks - 1


def test_deadline_expires_queued_request():
    from repro.obs import Observer

    model, params, _ = fuzz._setup("dense")
    obs = Observer()

    async def scenario():
        fe = AsyncEngine(engine=Engine(model, params, slots=1, max_len=96,
                                       block_size=8, prefill_chunk=8, obs=obs))
        ok = fe.submit([1, 2, 3], max_tokens=6)
        doomed = fe.submit([4, 5, 6], max_tokens=6, deadline_s=1e-9)
        toks = [t async for t in doomed.stream()]
        await fe.drain()
        return ok, doomed, toks

    ok, doomed, toks = asyncio.run(scenario())
    assert ok.done and not ok.cancelled and len(ok.out_tokens) == 6
    assert doomed.cancelled and doomed.finish_reason == "deadline"
    assert toks == [] and doomed.out_tokens == []
    assert obs.registry.get("serve_deadline_miss_total").value == 1
    assert obs.registry.get("serve_cancellations_total").value == 1
    assert [e["rid"] for e in obs.trace.by_type("deadline_miss")] == [doomed.rid]


def test_submit_validation():
    model, params, _ = fuzz._setup("dense")
    fe = AsyncEngine(model, params, slots=1, max_len=96, prefill_chunk=8)
    # outside an event loop: no handle, no queued request
    with pytest.raises(RuntimeError):
        fe.submit([1, 2, 3])
    assert not fe.engine.pending()

    async def scenario():
        for bad in (0, -1.5):
            with pytest.raises(ValueError, match="deadline_s"):
                fe.submit([1, 2, 3], max_tokens=4, deadline_s=bad)
        assert not fe.engine.pending()  # rejected before enqueue
        with pytest.raises(ValueError):
            fe.submit([], max_tokens=4)

    asyncio.run(scenario())
    with pytest.raises(ValueError, match="prebuilt engine"):
        AsyncEngine(model, params, engine=fe.engine)


def test_drained_engine_reuse():
    """After the pump drains, a later submit restarts it — the front-end is
    never silently stale."""
    model, params, _ = fuzz._setup("dense")
    prompt = [9, 9, 1]
    expected = _ref(model, params, prompt, 5)

    async def scenario():
        fe = AsyncEngine(model, params, slots=1, max_len=96, prefill_chunk=8)
        first = await fe.submit(prompt, max_tokens=5).result()
        await fe.drain()
        pump1 = fe._pump_task
        assert pump1.done()
        second = await fe.submit(prompt, max_tokens=5).result()
        await fe.drain()
        assert fe._pump_task is not pump1  # fresh pump, not the stale one
        return first, second

    first, second = asyncio.run(scenario())
    assert first == expected and second == expected


def test_frontend_smoke():
    """CI smoke (pallas-interpret matrix): two concurrent streams, one
    cancelled, tokens identical to the solo reference."""
    model, params, reference = fuzz._setup("dense")
    p1, p2 = [1, 2, 3, 4], [7, 6, 5]
    expected = reference(p1)[:6]

    async def scenario():
        fe = AsyncEngine(model, params, slots=2, max_len=96, block_size=8,
                         prefill_chunk=8)
        h1 = fe.submit(p1, max_tokens=6)
        h2 = fe.submit(p2, max_tokens=30)
        toks1 = [t async for t in h1.stream()]
        h2.cancel()
        await fe.drain()
        return toks1, h2

    toks1, h2 = asyncio.run(scenario())
    assert toks1 == expected
    assert h2.cancelled
