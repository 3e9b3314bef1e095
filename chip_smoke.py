"""Chip smoke run: serve chatglm3-6b at its published widths on one TPU.

    python chip_smoke.py

Drives the served path once, through the entry points a user calls: the
launcher's builder (``repro.launch.serve.build``: full depth and widths,
bfloat16 params drawn from a seed inside one jitted program), then ``Engine``
on the paged backend with four requests.  It checks that every request
finished, that the Pallas kernels (not the reference) served the TT and
attention roles, and that the kernels' logits agree with the pure-JAX
reference on the same chip.  Every phase prints one line; any failure exits
non-zero.  Without a TPU it exits non-zero before building anything: there
is no CPU fallback.  The last line of a passing run is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The seconds it prints are a smoke timing of one cold run, not a benchmark.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import dispatch  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import build  # noqa: E402
from repro.serve import steps  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402

ARCH = "chatglm3-6b"
SEED = 0
SLOTS, MAX_LEN, PREFILL_CHUNK = 4, 2048, 256
PROMPT_LENS = (128, 384, 640, 1024)
MAX_TOKENS = 16
CHECK_PROMPT_LEN = 200  # 13 KV blocks of 16, the last one ragged
ATTENTION_ROLES = ("attn_paged", "attn_prefill")
# Relative L2 error of the kernels' logits against the reference's.  Both
# paths keep bf16 activations with f32 accumulation, but a TT kernel rounds
# its output once after the fused epilogue where the reference rounds before
# it too, and summation orders differ; each such difference flips a bf16
# rounding somewhere, and the flips compound through the layers.  At the
# CPU test size (2 layers, width 64) that floor is about 1e-2: the kernels
# sit 0.9e-2 from the bf16 reference, which itself sits 1.2e-2 from a
# float32 run, and float32 runs of both agree to 4e-7.  The tolerance leaves
# room for 28 layers of that noise.  A kernel that drops a TT stage computes
# a different linear map (error of order 1); one that drops a KV block
# changes the attention output of every later position.  The run checks the
# second claim on the chip: it zeroes one KV block of the reference's cache
# and requires that error to exceed the tolerance too.
LOGITS_RTOL = 5e-2


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"FAIL: {msg}")


def prompt(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    return [int(t) for t in rng.integers(1, vocab, n)]


def serve(model, params, prompts, *, max_tokens: int, kernel_backend=None,
          slots: int = SLOTS, max_len: int = MAX_LEN,
          prefill_chunk: int = PREFILL_CHUNK):
    """Serve ``prompts`` on the paged backend; returns (engine, compile_s,
    serve_s).  A one-request warm-up compiles the prefill and decode
    programs first, so ``serve_s`` times the requests alone."""
    engine = Engine(model, params, slots=slots, max_len=max_len,
                    backend="paged", prefill_chunk=prefill_chunk,
                    cache_dtype="bfloat16", kernel_backend=kernel_backend)
    t0 = time.perf_counter()
    engine.submit(prompts[0][:8], max_tokens=2)
    engine.run()
    compile_s = time.perf_counter() - t0
    reqs = [engine.submit(p, max_tokens=max_tokens) for p in prompts]
    t0 = time.perf_counter()
    done = engine.run()
    serve_s = time.perf_counter() - t0
    for r in reqs:
        if not r.done or len(r.out_tokens) != max_tokens:
            fail(f"request {r.rid} ({len(r.prompt)} prompt tokens) ended "
                 f"with {len(r.out_tokens)}/{max_tokens} tokens "
                 f"({r.finish_reason or 'unfinished'})")
    if len(done) != len(reqs):
        fail(f"{len(done)} of {len(reqs)} requests finished")
    return engine, compile_s, serve_s


def kernel_roles() -> dict[str, set[str]]:
    """{role: backends it resolved to} for every role that dispatched to a
    kernel (dense linears resolve to ``xla`` and are left out).  Read from
    the per-(role, backend) counters rather than ``resolved_backend``: that
    keeps only the last resolution, and the dense blocks' linears share the
    TT roles' names."""
    out: dict[str, set[str]] = {}
    for (role, backend), n in dispatch.dispatch_counts().items():
        if n and backend != "xla":
            out.setdefault(role, set()).add(backend)
    return out


def check_backends(tt_roles, expect: str) -> dict[str, set[str]]:
    """Every kernel role the run traced resolved to ``expect`` only, and the
    TT and attention roles are among them."""
    roles = kernel_roles()
    missing = [r for r in (*tt_roles, *ATTENTION_ROLES) if r not in roles]
    if missing:
        fail(f"roles never dispatched to a kernel: {missing}")
    wrong = {r: sorted(b) for r, b in roles.items() if b != {expect}}
    if wrong:
        fail(f"roles not served by {expect!r}: {wrong}")
    return roles


def rel_err(a, b) -> float:
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check_logits(session, params, tokens: list[int], *, kernel_backend,
                 rtol: float = LOGITS_RTOL) -> dict[str, float]:
    """Prefill + first decode step of one prompt through the kernels and the
    reference; returns the relative errors, failing past ``rtol``."""
    sp = session.spec
    n = len(tokens)
    if n >= sp.prefill_chunk:
        fail(f"check prompt of {n} tokens must fit one {sp.prefill_chunk} chunk")
    n_blocks = -(-(n + 1) // sp.block_size)
    table = np.zeros((sp.slots, sp.table_width()), np.int32)
    table[0, :n_blocks] = np.arange(1, n_blocks + 1)
    toks = np.zeros((sp.slots, sp.prefill_chunk), np.int32)
    pos = np.full((sp.slots, sp.prefill_chunk), -1, np.int32)
    toks[0, :n], pos[0, :n] = tokens, np.arange(n)
    dec_pos = np.full((sp.slots,), -1, np.int32)
    dec_pos[0] = n

    out = {}
    for name, backend in (("kernel", kernel_backend), ("ref", "ref")):
        prefill, decode, _ = steps.session_step_fns(session, backend)
        state = session.with_tables(session.init_state(), table)
        logits, state = prefill(params, state, jnp.asarray(toks),
                                jnp.asarray(pos))
        out[name] = {"prefill": logits[0, :n], "state": state}
    nxt = np.zeros((sp.slots, 1), np.int32)
    nxt[0, 0] = int(jnp.argmax(out["ref"]["prefill"][-1]))  # same token for both
    for name, backend in (("kernel", kernel_backend), ("ref", "ref")):
        _, decode, _ = steps.session_step_fns(session, backend)
        logits, _ = decode(params, out[name]["state"], jnp.asarray(nxt),
                           jnp.asarray(dec_pos))
        out[name]["decode"] = logits[0]
    # control: the reference with one KV block of the prompt zeroed
    _, decode, _ = steps.session_step_fns(session, "ref")
    blk = int(table[0, n_blocks // 2])
    zeroed = dict(out["ref"]["state"], kv=jax.tree.map(
        lambda a: a.at[:, blk].set(0), out["ref"]["state"]["kv"]))
    logits, _ = decode(params, zeroed, jnp.asarray(nxt), jnp.asarray(dec_pos))

    errs = {"prefill": rel_err(out["kernel"]["prefill"], out["ref"]["prefill"]),
            "decode": rel_err(out["kernel"]["decode"], out["ref"]["decode"]),
            "zeroed_block_control": rel_err(logits, out["ref"]["decode"])}
    if not np.isfinite(list(errs.values())).all():
        fail(f"non-finite logits: {errs}")
    if errs["prefill"] > rtol or errs["decode"] > rtol:
        fail(f"kernel logits differ from the reference: {errs} > {rtol}")
    if errs["zeroed_block_control"] <= rtol:
        fail(f"tolerance {rtol} cannot see a lost KV block: {errs}")
    return errs


def main() -> int:
    dev = jax.devices()[0]
    count = len(jax.devices())
    say("device", f"platform={dev.platform} kind={dev.device_kind} count={count}")
    if dev.platform != "tpu":
        fail(f"no TPU (JAX found {dev.platform!r}); this run has no CPU fallback")

    say("cache", f"persistent compilation cache at {use_compile_cache()}")

    t0 = time.perf_counter()
    cfg, model, params = build(ARCH, seed=SEED)
    leaves = jax.tree.leaves(params)
    jax.block_until_ready(leaves)
    n_params = sum(x.size for x in leaves)
    param_bytes = sum(x.nbytes for x in leaves)
    stats = dev.memory_stats() or {}
    say("build", f"{cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} param_dtype={cfg.param_dtype} "
        f"tt_blocks={cfg.ttd.first_tt_block}..{cfg.n_layers - 1}; "
        f"{n_params} params, {param_bytes} bytes, device bytes_in_use="
        f"{stats.get('bytes_in_use')} ({time.perf_counter() - t0:.1f}s)")

    rng = np.random.default_rng(SEED)
    prompts = [prompt(rng, n, cfg.vocab_size) for n in PROMPT_LENS]
    dispatch.reset_dispatch_metrics()
    engine, compile_s, serve_s = serve(model, params, prompts,
                                       max_tokens=MAX_TOKENS)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    say("serve", f"{len(prompts)} requests x {MAX_TOKENS} tokens finished "
        f"(prompts {list(PROMPT_LENS)}); smoke timing, not a benchmark: "
        f"compile+warm-up {compile_s:.1f}s, serve {serve_s:.1f}s; "
        f"peak_bytes_in_use={peak}")

    tt_roles = sorted({r for r, _ in cfg.ttd.overrides})
    roles = check_backends(tt_roles, "pallas")
    say("kernels", ", ".join(f"{r}={'/'.join(sorted(b))}"
                             for r, b in sorted(roles.items())))

    errs = check_logits(engine.session, params,
                        prompt(rng, CHECK_PROMPT_LEN, cfg.vocab_size),
                        kernel_backend=None)
    say("logits", "pallas vs ref relative L2 error: "
        + ", ".join(f"{k}={v:.3e}" for k, v in errs.items())
        + f" (tolerance {LOGITS_RTOL})")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
