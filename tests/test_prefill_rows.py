"""A prefill tile of only the admitted rows computes what the full tile does.

On a paged session the block table is the only per-slot state, so a chunk
call over ``(rows, chunk)`` that names each row's slot must give the same
last-token logits and the same K/V pool as the ``(slots, chunk)`` tile in
which every other slot rides along at position ``-1``.  Both run through
the jitted program of ``session_step_fns``, the one the engine serves with.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import SessionSpec, build_model, make_session
from repro.serve.steps import chunked_prefill, session_step_fns

CHUNK, BLOCK, MAX_LEN = 8, 4, 48


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("slots,admitted,lens,rows", [
    (4, [1], [11], 1),            # one admitted row, a tile of one
    (4, [1], [11], 2),            # one admitted row and a padding row
    (4, [0, 1], [5, 7], 2),       # two rows at the front
    (8, [2, 5], [7, 20], 2),      # slots away from the front, three chunks
    (8, [6, 3], [30, 9], 2),      # rows out of slot order, four chunks
], ids=["1of1", "1of2", "front", "slots2and5", "unordered"])
def test_compact_tile_matches_full_tile(slots, admitted, lens, rows):
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    sess = make_session(cfg, SessionSpec(slots=slots, max_len=MAX_LEN,
                                         prefill_chunk=CHUNK, block_size=BLOCK))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    prefill, _, _ = session_step_fns(sess)
    state = sess.init_state()
    # every slot owns its own blocks; the pool starts with noise, standing
    # for what earlier sequences wrote, which neither tile may disturb
    width = sess.spec.table_width()
    tables = 1 + np.arange(slots * width, dtype=np.int32).reshape(slots, width)
    noise = jax.random.split(jax.random.PRNGKey(1), len(state["kv"]))
    kv = [{n: jax.random.normal(jax.random.fold_in(k, i), seg[n].shape,
                                seg[n].dtype)
           for i, n in enumerate(sorted(seg))}
          for k, seg in zip(noise, state["kv"])]
    state = sess.with_tables(dict(state, kv=kv), tables)
    rng = np.random.default_rng(sum(lens))
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)] for n in lens]

    full_prompts = [None] * slots
    for s, p in zip(admitted, prompts):
        full_prompts[s] = p
    full_logits, full_state = chunked_prefill(prefill, params, state,
                                              full_prompts, chunk=CHUNK)
    pad = rows - len(admitted)
    logits, compact_state = chunked_prefill(
        prefill, params, state, prompts + [None] * pad, chunk=CHUNK,
        slots=admitted + [0] * pad)

    assert logits.shape == (rows, cfg.vocab_size)
    for i, s in enumerate(admitted):
        assert _rel(logits[i], full_logits[s]) <= 1e-6, (i, s)
    np.testing.assert_array_equal(np.asarray(compact_state["block_tables"]),
                                  tables)
    for seg_c, seg_f in zip(compact_state["kv"], full_state["kv"]):
        for name in seg_f:
            # block 0 is the null block: padding writes land there and it is
            # never read, so only the owned blocks are compared
            c, f = seg_c[name][:, 1:], seg_f[name][:, 1:]
            assert _rel(c, f) <= 1e-6, name
    # the prompts' blocks were written: the pools moved off the noise
    moved = _rel(full_state["kv"][0]["k"][:, 1:], kv[0]["k"][:, 1:])
    assert moved > 0


def test_prefill_chunk_default_is_every_slot_in_order():
    """Without ``slots`` a paged session reads every slot's table in order:
    passing ``0..slots-1`` explicitly is the same call."""
    cfg = get_config("tinyllama-1.1b", reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    spec = SessionSpec(slots=2, max_len=32, prefill_chunk=CHUNK, block_size=BLOCK)
    sess = make_session(cfg, spec)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    tables = 1 + np.arange(2 * spec.table_width(), dtype=np.int32).reshape(2, -1)
    state = sess.with_tables(sess.init_state(), tables)
    toks = jnp.asarray([[5, 6, 7, 0, 0, 0, 0, 0], [9, 8, 7, 6, 5, 0, 0, 0]],
                       jnp.int32)
    pos = jnp.asarray([[0, 1, 2, -1, -1, -1, -1, -1],
                       [0, 1, 2, 3, 4, -1, -1, -1]], jnp.int32)
    a, sa = sess.prefill_chunk(params, state, toks, pos)
    b, sb = sess.prefill_chunk(params, state, toks, pos,
                               jnp.arange(2, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), sa, sb)
