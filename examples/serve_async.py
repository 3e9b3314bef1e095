"""Stream tokens from the asyncio serving front-end — with a mid-stream
cancel.

    PYTHONPATH=src python examples/serve_async.py

Two requests are submitted concurrently to :class:`repro.serve.AsyncEngine`
(DESIGN.md §12).  The first is streamed to completion with ``async for``;
the second is cancelled after its first few tokens arrive, which frees its
decode slot and KV blocks mid-flight.  The example asserts

* the completed stream is token-identical to generating the same prompt
  alone via ``model.prefill`` + ``model.decode_step``,
* the cancelled stream is a strict prefix of its solo reference and is
  marked ``cancelled`` with ``finish_reason == "user"``,
* the engine overlapped host and device work (dispatch-ahead ticks fired).
"""
import asyncio

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model
from repro.obs import Observer
from repro.serve import AsyncEngine


def reference(model, params, prompt, n):
    logits, cache = model.prefill(params, {"tokens": jnp.asarray([prompt], jnp.int32)},
                                  cache_dtype=jnp.float32, max_len=96)
    out = [int(jnp.argmax(logits[0]))]
    for pos in range(len(prompt), len(prompt) + n - 1):
        logits, cache = model.decode_step(
            params, cache, {"tokens": jnp.asarray([[out[-1]]], jnp.int32)},
            jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0])))
    return out


async def main():
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    obs = Observer()  # counters, scheduler events and spans
    frontend = AsyncEngine(model, params, slots=2, max_len=96,
                           block_size=8, prefill_chunk=8, obs=obs)
    keep = frontend.submit([1, 2, 3, 4, 5], max_tokens=20)
    drop = frontend.submit([7, 8, 9], max_tokens=20)

    async def stream_all(handle):
        toks = []
        async for tok in handle.stream():
            toks.append(tok)
        return toks

    async def stream_then_cancel(handle, after):
        toks = []
        async for tok in handle.stream():
            toks.append(tok)
            if len(toks) == after:
                handle.cancel()  # frees the slot + KV blocks mid-flight
        return toks

    kept, dropped = await asyncio.gather(stream_all(keep),
                                         stream_then_cancel(drop, after=3))
    await frontend.drain()

    assert kept == reference(model, params, [1, 2, 3, 4, 5], 20)
    solo = reference(model, params, [7, 8, 9], 20)
    assert dropped == solo[:len(dropped)] and len(dropped) < len(solo)
    assert drop.cancelled and drop.finish_reason == "user"
    ahead = obs.registry.get("serve_ahead_ticks_total").value
    ticks = obs.registry.get("serve_decode_ticks_total").value
    assert ahead > 0  # double buffering engaged

    print(f"streamed {len(kept)} tokens (identical to the solo reference); "
          f"cancelled the second request after {len(dropped)} tokens "
          f"(a strict prefix of its reference)")
    print(f"dispatch-ahead ticks: {ahead:.0f}/{ticks:.0f}")
    print("OK")


if __name__ == "__main__":
    asyncio.run(main())
