"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TTSpec, init_tt_linear, quantize_int4
from repro.kernels import dispatch, ref
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.prefill_attention import prefill_attention_pallas
from repro.kernels.scan_rglru import rglru_scan_pallas
from repro.kernels.scan_wkv import wkv_scan_pallas
from repro.kernels.tt_linear import pick_block_b, tt_linear_pallas
from repro.models.modules import attention_dense


@pytest.mark.parametrize("n,m,r,d,b,dtype", [
    (256, 512, 8, 4, 7, jnp.float32),
    (4096, 4096, 16, 4, 32, jnp.float32),   # paper LinearO
    (512, 256, 4, 3, 64, jnp.bfloat16),
    (64, 64, 2, 2, 1, jnp.float32),
    (2048, 5632, 8, 4, 13, jnp.bfloat16),   # tinyllama MLP shape
])
def test_tt_kernel_matches_ref(n, m, r, d, b, dtype, key):
    spec = TTSpec.make(n, m, r, d=d)
    cores = [c.astype(dtype) for c in init_tt_linear(key, spec, jnp.float32)["cores"]]
    x = jax.random.normal(key, (b, n), jnp.float32).astype(dtype)
    y_k = tt_linear_pallas(x, cores, spec, interpret=True).astype(jnp.float32)
    y_r = ref.tt_linear_staged(x, cores, spec).astype(jnp.float32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    scale = float(jnp.max(jnp.abs(y_r))) or 1.0
    assert float(jnp.max(jnp.abs(y_k - y_r))) / scale < tol


def test_tt_kernel_paper_factorization(key):
    spec = TTSpec.make(4096, 13696, 16, in_modes=(8, 8, 8, 8), out_modes=(4, 4, 8, 107))
    cores = init_tt_linear(key, spec, jnp.float32)["cores"]
    x = jax.random.normal(key, (16, 4096))
    y_k = tt_linear_pallas(x, cores, spec, interpret=True)
    y_r = ref.tt_linear_staged(x, cores, spec)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-4, atol=1e-4)


def test_tt_kernel_fused_bn_res_epilogue(key):
    """The paper's TTDLinear-BN-Res operator fusion (§III.A)."""
    spec = TTSpec.make(256, 512, 8, d=4)
    cores = init_tt_linear(key, spec, jnp.float32)["cores"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (10, 256))
    sc = jax.random.normal(k2, (512,))
    bi = jax.random.normal(k3, (512,))
    res = jax.random.normal(k4, (10, 512))
    y_k = tt_linear_pallas(x, cores, spec, scale=sc, bias=bi, residual=res, interpret=True)
    y_r = ref.tt_linear_bn_res(x, cores, spec, scale=sc, bias=bi, residual=res)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-5, atol=1e-5)


def test_tt_kernel_bias_only_epilogue(key):
    """bias without scale must still be applied in-kernel (regression: the
    old epilogue only handled bias through the "bn" branch)."""
    spec = TTSpec.make(256, 512, 8, d=4)
    cores = init_tt_linear(key, spec, jnp.float32)["cores"]
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (10, 256))
    bi = jax.random.normal(k2, (512,))
    y_k = tt_linear_pallas(x, cores, spec, bias=bi, interpret=True)
    y_r = ref.tt_linear_bn_res(x, cores, spec, bias=bi)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-5, atol=1e-5)
    # and the bias really landed (vs the silently-dropped behaviour)
    y_no = tt_linear_pallas(x, cores, spec, interpret=True)
    assert float(jnp.max(jnp.abs(y_k - (y_no + bi)))) < 1e-5
    assert float(jnp.max(jnp.abs(y_k - y_no))) > 1e-3


def test_tt_kernel_fused_activation(key):
    spec = TTSpec.make(256, 512, 8, d=4)
    cores = init_tt_linear(key, spec, jnp.float32)["cores"]
    x = jax.random.normal(key, (6, 256))
    y_k = tt_linear_pallas(x, cores, spec, activation="gelu", interpret=True)
    y_r = ref.tt_linear_bn_res(x, cores, spec, activation="gelu")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-5, atol=1e-5)


def test_tt_kernel_block_picker():
    spec = TTSpec.make(4096, 13696, 16, in_modes=(8, 8, 8, 8), out_modes=(4, 4, 8, 107))
    bb = pick_block_b(spec, 1024)
    assert bb >= 1 and (bb & (bb - 1)) == 0  # power of two
    per_token = (spec.n_in + spec.n_out + 2 * spec.max_intermediate()) * 4
    assert bb * per_token <= 12 * 2**20  # VMEM budget honored


def test_tt_kernel_block_picker_uses_dtype_bytes():
    """The VMEM footprint (cores included) must scale with the element size:
    halving dtype_bytes must never shrink the chosen block."""
    spec = TTSpec.make(4096, 13696, 16, in_modes=(8, 8, 8, 8), out_modes=(4, 4, 8, 107))
    bb4 = pick_block_b(spec, 4096, dtype_bytes=4)
    bb2 = pick_block_b(spec, 4096, dtype_bytes=2)
    assert bb2 >= bb4
    # fp16/bf16 budget accounting: cores also counted at dtype_bytes
    per_token = (spec.n_in + spec.n_out + 2 * spec.max_intermediate()) * 2
    assert bb2 * per_token + spec.n_params() * 2 <= 12 * 2**20


@pytest.mark.parametrize("b,block_b,dtype,use_res", [
    (7, 4, jnp.float32, True),    # pad 7 -> 8, residual padded too
    (13, 8, jnp.bfloat16, True),  # pad 13 -> 16
    (5, 8, jnp.float32, False),   # batch smaller than one block
    (9, 2, jnp.float32, True),    # odd batch, tiny block
])
def test_tt_kernel_padding_with_fused_epilogue(b, block_b, dtype, use_res, key):
    """Batch not divisible by block_b combined with the scale/bias(/residual)
    epilogue, checked against the kernels/ref.py oracle."""
    spec = TTSpec.make(256, 512, 8, d=4)
    cores = [c.astype(dtype) for c in init_tt_linear(key, spec, jnp.float32)["cores"]]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (b, 256), jnp.float32).astype(dtype)
    sc = jax.random.normal(k2, (512,), jnp.float32).astype(dtype)
    bi = jax.random.normal(k3, (512,), jnp.float32).astype(dtype)
    res = jax.random.normal(k4, (b, 512), jnp.float32).astype(dtype) if use_res else None
    y_k = tt_linear_pallas(x, cores, spec, scale=sc, bias=bi, residual=res,
                           block_b=block_b, interpret=True)
    y_r = ref.tt_linear_bn_res(x, cores, spec, scale=sc, bias=bi, residual=res)
    assert y_k.shape == (b, 512)
    y_k32, y_r32 = y_k.astype(jnp.float32), y_r.astype(jnp.float32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    scale_ref = float(jnp.max(jnp.abs(y_r32))) or 1.0
    assert float(jnp.max(jnp.abs(y_k32 - y_r32))) / scale_ref < tol


@pytest.mark.parametrize("b,k,m,g,dtype", [
    (8, 256, 128, 64, jnp.float32),
    (130, 4096, 300, 128, jnp.bfloat16),
    (1, 512, 512, 128, jnp.float32),
    (33, 1024, 96, 256, jnp.bfloat16),
])
def test_int4_kernel_matches_ref(b, k, m, g, dtype, key):
    w = np.random.randn(m, k).astype(np.float32)
    q = quantize_int4(w, g)
    x = jax.random.normal(key, (b, k), jnp.float32).astype(dtype)
    y_k = int4_matmul_pallas(x, q["qweight"], q["scales"], group=g, interpret=True)
    y_r = ref.int4_matmul(x, q["qweight"], q["scales"], group=g)
    scale = float(jnp.max(jnp.abs(y_r.astype(jnp.float32)))) or 1.0
    err = float(jnp.max(jnp.abs(y_k.astype(jnp.float32) - y_r.astype(jnp.float32))))
    assert err / scale < 2e-2


@pytest.mark.parametrize("b,k,m,use_scale", [
    (7, 256, 130, False),   # padded batch AND padded out-features
    (16, 256, 128, True),
])
def test_int4_kernel_fused_epilogue(b, k, m, use_scale, key):
    """int4 kernel's bias(/scale)+residual epilogue vs the oracle, including
    m-padding where epilogue columns must be padded alongside qweight."""
    g = 64
    w = np.random.randn(m, k).astype(np.float32)
    q = quantize_int4(w, g)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (b, k), jnp.float32)
    sc = jax.random.normal(k2, (m,)) if use_scale else None
    bi = jax.random.normal(k3, (m,))
    res = jax.random.normal(k4, (b, m))
    y_k = int4_matmul_pallas(x, q["qweight"], q["scales"], group=g, scale=sc,
                             bias=bi, residual=res, interpret=True)
    y_r = ref.int4_matmul(x, q["qweight"], q["scales"], group=g, scale=sc,
                          bias=bi, residual=res)
    assert y_k.shape == (b, m)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Paged decode attention (serve path) — kernel vs gather oracle vs dense math
# ---------------------------------------------------------------------------
def _paged_case(seed, *, block_size, ctx_lens, hkv=2, g=2, dh=16,
                cache_dtype=jnp.float32):
    """Random paged cache with each sequence's context scattered over a
    shuffled block pool; returns (q, cache, block_tables, qpos)."""
    rng = np.random.default_rng(seed)
    b, h = len(ctx_lens), hkv * g
    w = max(1, max((c + block_size - 1) // block_size for c in ctx_lens))
    nb = 1 + sum((c + block_size - 1) // block_size for c in ctx_lens) + 2
    shape = (nb, block_size, hkv, dh)
    if cache_dtype == jnp.int8:
        cache = {
            "k": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "k_scale": jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32),
            "v_scale": jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32),
        }
    else:
        cache = {
            "k": jnp.asarray(rng.standard_normal(shape), cache_dtype),
            "v": jnp.asarray(rng.standard_normal(shape), cache_dtype),
        }
    pool = list(rng.permutation(np.arange(1, nb)))
    bt = np.zeros((b, w), np.int32)
    for i, c in enumerate(ctx_lens):
        for j in range((c + block_size - 1) // block_size):
            bt[i, j] = pool.pop()
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    qpos = jnp.asarray(np.asarray(ctx_lens, np.int32) - 1)
    return q, cache, jnp.asarray(bt), qpos


@pytest.mark.parametrize("block_size,ctx_lens,cache_dtype", [
    (4, (7, 4, 0, 1), jnp.float32),    # ragged last block + empty + singleton
    (8, (16, 3, 9), jnp.float32),      # exact block multiple + ragged
    (16, (5,), jnp.float32),           # context smaller than one block
    (4, (13, 8, 1), jnp.float16),
    (8, (12, 5), jnp.bfloat16),
    (4, (6, 2, 0), jnp.int8),          # per-block-scale dequant + empty seq
    (8, (17, 1), jnp.int8),
])
def test_paged_attention_kernel_parity(block_size, ctx_lens, cache_dtype):
    """Fused online-softmax kernel vs the gather oracle across block sizes ×
    seq lens × cache dtypes, including the ragged-last-block and
    empty-sequence (qpos = -1) edge cases."""
    q, cache, bt, qpos = _paged_case(block_size * 131 + len(ctx_lens),
                                     block_size=block_size, ctx_lens=ctx_lens,
                                     cache_dtype=cache_dtype)
    y_k = paged_attention_pallas(q, cache, bt, qpos, interpret=True)
    y_r = ref.paged_attention(q[:, None], cache, bt, qpos[:, None])[:, 0]
    tol = 1e-5 if cache_dtype in (jnp.float32, jnp.int8) else 3e-2
    scale = float(jnp.max(jnp.abs(y_r))) or 1.0
    assert float(jnp.max(jnp.abs(y_k - y_r))) / scale < tol
    # empty sequences must return exactly zero from both paths
    for i, c in enumerate(ctx_lens):
        if c == 0:
            assert float(jnp.max(jnp.abs(y_k[i]))) == 0.0
            assert float(jnp.max(jnp.abs(y_r[i]))) == 0.0


def test_paged_attention_dispatch_backends():
    """ref and pallas-interpret agree through the dispatch layer (the policy
    chain the serve engine pins)."""
    q, cache, bt, qpos = _paged_case(7, block_size=4, ctx_lens=(9, 2, 0))
    y_ref = dispatch.paged_attention(q, cache, bt, qpos, backend="ref")
    y_pl = dispatch.paged_attention(q, cache, bt, qpos, backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_ref_matches_dense_attention():
    """The gather oracle itself vs models.modules.attention_dense on a
    contiguous (identity block table) layout — ties the paged math back to
    the attention used everywhere else."""
    rng = np.random.default_rng(3)
    bs, ctx, hkv, g, dh = 4, 11, 2, 2, 16
    nb = 1 + (ctx + bs - 1) // bs
    k = rng.standard_normal((nb, bs, hkv, dh)).astype(np.float32)
    v = rng.standard_normal((nb, bs, hkv, dh)).astype(np.float32)
    cache = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    bt = jnp.asarray(np.arange(1, nb, dtype=np.int32)[None])  # in-order blocks
    q = jnp.asarray(rng.standard_normal((1, hkv * g, dh)), jnp.float32)
    y_p = ref.paged_attention(q[:, None], cache, bt, jnp.asarray([[ctx - 1]]))[:, 0]
    kf = jnp.asarray(k[1:].reshape(1, -1, hkv, dh))
    vf = jnp.asarray(v[1:].reshape(1, -1, hkv, dh))
    kpos = jnp.arange(kf.shape[1], dtype=jnp.int32)
    y_d = attention_dense(q[:, None], kf, vf, qpos=jnp.asarray([ctx - 1]),
                          kpos=kpos, kmask=kpos < ctx)[:, 0]
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_d),
                               rtol=1e-5, atol=1e-5)


def test_paged_int8_write_read_roundtrip():
    """paged_kv_update's int8 quantization round-trips through the oracle
    within int8 rounding error."""
    from repro.models.modules import paged_kv_update
    rng = np.random.default_rng(11)
    bs, hkv, dh = 4, 2, 8
    cache = {
        "k": jnp.zeros((4, bs, hkv, dh), jnp.int8),
        "v": jnp.zeros((4, bs, hkv, dh), jnp.int8),
        "k_scale": jnp.zeros((4, bs, hkv), jnp.float32),
        "v_scale": jnp.zeros((4, bs, hkv), jnp.float32),
    }
    bt = jnp.asarray([[1, 2]], jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((1, 6, hkv, dh)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((1, 6, hkv, dh)), jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    cache = paged_kv_update(cache, k_new, v_new, bt, pos)
    k_rt, v_rt = ref.gather_paged_kv(cache, bt)
    np.testing.assert_allclose(np.asarray(k_rt[0, :6]), np.asarray(k_new[0]),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(v_rt[0, :6]), np.asarray(v_new[0]),
                               atol=2e-2)


# ---------------------------------------------------------------------------
# Ragged chunked-prefill flash attention — kernel vs the ref.py oracles over
# both cache layouts (paged block pools / per-slot rings)
# ---------------------------------------------------------------------------
def _prefill_qpos(ctx_lens, chunk):
    """(B, chunk) query positions: each row holds the last ``min(chunk, c)``
    positions of its sequence, tail-padded with -1 (idle rows all -1)."""
    qpos = np.full((len(ctx_lens), chunk), -1, np.int32)
    for i, c in enumerate(ctx_lens):
        n = min(chunk, c)
        qpos[i, :n] = np.arange(c - n, c)
    return jnp.asarray(qpos)


def _prefill_paged_case(seed, *, block_size, ctx_lens, chunk, hkv=2, g=2,
                        dh=16, cache_dtype=jnp.float32, q_dtype=jnp.float32):
    """Random paged pool covering every context position, shuffled block ids;
    returns (q, cache, block_tables, qpos)."""
    rng = np.random.default_rng(seed)
    b, h = len(ctx_lens), hkv * g
    w = max(1, max((c + block_size - 1) // block_size for c in ctx_lens))
    nb = 1 + sum((c + block_size - 1) // block_size for c in ctx_lens) + 2
    shape = (nb, block_size, hkv, dh)
    if cache_dtype == jnp.int8:
        cache = {
            "k": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "k_scale": jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32),
            "v_scale": jnp.asarray(rng.uniform(0.005, 0.02, shape[:-1]), jnp.float32),
        }
    else:
        cache = {
            "k": jnp.asarray(rng.standard_normal(shape), cache_dtype),
            "v": jnp.asarray(rng.standard_normal(shape), cache_dtype),
        }
    pool = list(rng.permutation(np.arange(1, nb)))
    bt = np.zeros((b, w), np.int32)
    for i, c in enumerate(ctx_lens):
        for j in range((c + block_size - 1) // block_size):
            bt[i, j] = pool.pop()
    q = jnp.asarray(rng.standard_normal((b, chunk, h, dh)), jnp.float32).astype(q_dtype)
    return q, cache, jnp.asarray(bt), _prefill_qpos(ctx_lens, chunk)


def _prefill_ring_case(seed, *, ring_width, ctx_lens, chunk, hkv=2, g=2,
                       dh=16, cache_dtype=jnp.float32, q_dtype=jnp.float32):
    """Random per-slot rings in ring layout (position p at slot p % WR);
    returns (q, k, v, kpos, qpos)."""
    rng = np.random.default_rng(seed)
    b, h = len(ctx_lens), hkv * g
    k = jnp.asarray(rng.standard_normal((b, ring_width, hkv, dh)), cache_dtype)
    v = jnp.asarray(rng.standard_normal((b, ring_width, hkv, dh)), cache_dtype)
    kpos = np.full((b, ring_width), -1, np.int32)
    for i, c in enumerate(ctx_lens):
        for p in range(max(0, c - ring_width), c):
            kpos[i, p % ring_width] = p
    q = jnp.asarray(rng.standard_normal((b, chunk, h, dh)), jnp.float32).astype(q_dtype)
    return q, k, v, jnp.asarray(kpos), _prefill_qpos(ctx_lens, chunk)


def _assert_close(y_k, y_r, tol):
    y_k = jnp.asarray(y_k, jnp.float32)
    y_r = jnp.asarray(y_r, jnp.float32)
    scale = float(jnp.max(jnp.abs(y_r))) or 1.0
    assert float(jnp.max(jnp.abs(y_k - y_r))) / scale < tol


@pytest.mark.parametrize("block_size,ctx_lens,chunk,g,cache_dtype", [
    (4, (11, 3, 0), 5, 2, jnp.float32),    # ragged + idle row, mid-chunk
    (8, (16, 7, 1), 8, 1, jnp.float32),    # MHA (g=1), exact block multiple
    (4, (9, 2), 3, 4, jnp.float32),        # wide GQA group
    (4, (13, 5, 0), 6, 2, jnp.float16),
    (8, (12, 4), 7, 2, jnp.bfloat16),
    (4, (10, 1, 0), 4, 2, jnp.int8),       # fused per-slot-scale dequant
    (8, (17, 6), 9, 3, jnp.int8),
])
def test_prefill_attention_paged_parity(block_size, ctx_lens, chunk, g, cache_dtype):
    """Streaming prefill kernel vs the gather oracle: block sizes × context
    lens × chunk widths × GQA ratios × cache dtypes, with ragged tails,
    empty rows and shuffled block tables."""
    q_dtype = cache_dtype if cache_dtype in (jnp.float16, jnp.bfloat16) else jnp.float32
    q, cache, bt, qpos = _prefill_paged_case(
        block_size * 977 + chunk, block_size=block_size, ctx_lens=ctx_lens,
        chunk=chunk, g=g, cache_dtype=cache_dtype, q_dtype=q_dtype)
    y_k = prefill_attention_pallas(q, qpos, cache=cache, block_tables=bt,
                                   q_tile=4, interpret=True)
    y_r = ref.paged_attention(q, cache, bt, qpos)
    tol = 1e-5 if q_dtype == jnp.float32 else 3e-2
    _assert_close(y_k, y_r, tol)
    for i, c in enumerate(ctx_lens):
        if c == 0:  # idle rows are exactly zero on both paths
            assert float(jnp.max(jnp.abs(jnp.asarray(y_k, jnp.float32)[i]))) == 0.0
            assert float(jnp.max(jnp.abs(jnp.asarray(y_r, jnp.float32)[i]))) == 0.0


@pytest.mark.parametrize("ring_width,ctx_lens,chunk,g,window,cache_dtype", [
    (16, (11, 3, 0), 5, 2, 0, jnp.float32),    # full attention rings
    (12, (23, 9), 6, 2, 8, jnp.float32),       # SWA: ring wraps, window masks
    (8, (7, 2, 0), 4, 1, 4, jnp.float32),      # MHA + tiny window
    (16, (14, 5), 7, 4, 6, jnp.float32),       # wide GQA group + window
    (12, (19, 8, 1), 5, 2, 7, jnp.float16),
    (16, (21, 4), 8, 2, 9, jnp.bfloat16),
])
def test_prefill_attention_ring_parity(ring_width, ctx_lens, chunk, g, window,
                                       cache_dtype):
    """Streaming prefill kernel vs the ring oracle: ring widths × context
    lens × chunk widths × GQA ratios × sliding windows × cache dtypes,
    including wrapped rings and empty rows."""
    q_dtype = cache_dtype if cache_dtype in (jnp.float16, jnp.bfloat16) else jnp.float32
    q, k, v, kpos, qpos = _prefill_ring_case(
        ring_width * 389 + chunk, ring_width=ring_width, ctx_lens=ctx_lens,
        chunk=chunk, g=g, cache_dtype=cache_dtype, q_dtype=q_dtype)
    y_k = prefill_attention_pallas(q, qpos, k=k, v=v, kpos=kpos, window=window,
                                   q_tile=3, kv_tile=5, interpret=True)
    y_r = ref.ring_attention(q, k, v, qpos, kpos, window=window)
    tol = 1e-5 if q_dtype == jnp.float32 else 3e-2
    _assert_close(y_k, y_r, tol)


def test_prefill_attention_all_idle_rows():
    """A fully idle batch (every qpos -1) walks zero blocks and returns
    exactly zero from the kernel and both oracles."""
    q, cache, bt, _ = _prefill_paged_case(5, block_size=4, ctx_lens=(8, 3),
                                          chunk=4)
    qpos = jnp.full((2, 4), -1, jnp.int32)
    for y in (prefill_attention_pallas(q, qpos, cache=cache, block_tables=bt,
                                       interpret=True),
              ref.paged_attention(q, cache, bt, qpos)):
        assert float(jnp.max(jnp.abs(y))) == 0.0
    q, k, v, kpos, _ = _prefill_ring_case(6, ring_width=8, ctx_lens=(6, 2),
                                          chunk=4)
    for y in (prefill_attention_pallas(q, qpos, k=k, v=v, kpos=kpos,
                                       interpret=True),
              ref.ring_attention(q, k, v, qpos, kpos)):
        assert float(jnp.max(jnp.abs(y))) == 0.0


def test_prefill_attention_dispatch_backends():
    """ref and pallas-interpret agree through dispatch.prefill_attention for
    both layouts (the policy chain the serve engine pins)."""
    q, cache, bt, qpos = _prefill_paged_case(17, block_size=4,
                                             ctx_lens=(9, 2, 0), chunk=4)
    y_ref = dispatch.prefill_attention(q, qpos, cache=cache, block_tables=bt,
                                       backend="ref")
    y_pl = dispatch.prefill_attention(q, qpos, cache=cache, block_tables=bt,
                                      backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    q, k, v, kpos, qpos = _prefill_ring_case(18, ring_width=10,
                                             ctx_lens=(13, 4, 0), chunk=5)
    y_ref = dispatch.prefill_attention(q, qpos, k=k, v=v, kpos=kpos, window=6,
                                       backend="ref")
    y_pl = dispatch.prefill_attention(q, qpos, k=k, v=v, kpos=kpos, window=6,
                                      backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="exactly one layout"):
        dispatch.prefill_attention(q, qpos, backend="ref")
    with pytest.raises(ValueError, match="exactly one layout"):
        dispatch.prefill_attention(q, qpos, cache=cache, block_tables=bt,
                                   k=k, v=v, kpos=kpos, backend="ref")
    with pytest.raises(ValueError, match="paged layout needs"):
        dispatch.prefill_attention(q, qpos, cache=cache, backend="ref")
    with pytest.raises(ValueError, match="ring layout needs"):
        dispatch.prefill_attention(q, qpos, k=k, v=v, backend="ref")


def test_prefill_ring_oracle_matches_dense_attention():
    """The ring oracle vs models.modules.attention_dense on an unwrapped
    (identity-layout) ring — ties the ragged per-sequence math back to the
    attention used everywhere else, including the window mask."""
    rng = np.random.default_rng(21)
    ctx, chunk, hkv, g, dh, win = 9, 4, 2, 2, 16, 5
    k = jnp.asarray(rng.standard_normal((1, ctx, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, ctx, hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, chunk, hkv * g, dh)), jnp.float32)
    pos = jnp.arange(ctx, dtype=jnp.int32)
    qpos = pos[None, ctx - chunk:]
    y_o = ref.ring_attention(q, k, v, qpos, pos[None], window=win)
    y_d = attention_dense(q, k, v, qpos=qpos[0], kpos=pos, causal=True,
                          window=win)
    np.testing.assert_allclose(np.asarray(y_o), np.asarray(y_d),
                               rtol=1e-5, atol=1e-5)


def test_prefill_chunk_session_parity_ref_vs_interpret():
    """End-to-end: a full multi-layer chunked-prefill step (paged AND ring
    state backends) produces matching logits under ref and pallas-interpret
    — the exact programs serve.steps jits for the engine."""
    from repro.configs import get_config
    from repro.kernels.dispatch import backend_override
    from repro.models import build_model
    from repro.models.sessions import SessionSpec, make_session

    cfg = get_config("tinyllama-1.1b", reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = SessionSpec(slots=2, max_len=32, prefill_chunk=8, block_size=4)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    pos = np.full((2, 8), -1, np.int32)
    pos[0, :8] = np.arange(8)
    pos[1, :3] = np.arange(3)  # ragged second row
    pos = jnp.asarray(pos)
    for backend in ("paged", "ring"):
        session = make_session(cfg, spec, backend=backend)
        state = session.init_state()
        if backend == "paged":
            bt = np.zeros((2, spec.table_width()), np.int32)
            bt[0, :2], bt[1, :2] = (1, 2), (3, 4)
            state = session.with_tables(state, bt)
        outs = {}
        for kb in ("ref", "pallas-interpret"):
            with backend_override(kb):
                logits, _ = session.prefill_chunk(params, state, toks, pos)
            outs[kb] = np.asarray(logits)
        np.testing.assert_allclose(outs["pallas-interpret"], outs["ref"],
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Recurrent-scan kernels (RG-LRU / wkv) — Pallas kernels vs the kernels/ref.py
# oracles across dtypes × tile/chunk widths × ragged/idle rows, then ref vs
# pallas-interpret through the dispatch layer and a full session-level sweep.
# ---------------------------------------------------------------------------
def _scan_pos(ctx_lens, s):
    """(B, S) positions: row i holds ``min(ctx_lens[i], s)`` real steps then
    -1 padding (0-length rows are fully idle)."""
    pos = np.full((len(ctx_lens), s), -1, np.int32)
    for i, c in enumerate(ctx_lens):
        n = min(c, s)
        pos[i, :n] = np.arange(n)
    return jnp.asarray(pos)


@pytest.mark.parametrize("s,w,ctx_lens,scan_dtype,tt,wt", [
    (8, 16, (8, 3, 0), jnp.float32, 4, 8),      # ragged + idle row
    (16, 40, (16, 16), jnp.float32, 16, 128),   # full rows, tile wider than W
    (7, 24, (7, 2, 0), jnp.float32, 4, 16),     # odd S padded to token tile
    (12, 48, (12, 5), jnp.bfloat16, 8, 32),     # bf16 scan carries
    (6, 8, (0, 0), jnp.float32, 2, 8),          # fully-idle batch
])
def test_rglru_scan_prefill_parity(s, w, ctx_lens, scan_dtype, tt, wt, key):
    """Chunked-prefill RG-LRU kernel vs the associative-scan oracle across
    scan dtypes × token/width tiles × ragged and fully-idle rows."""
    b = len(ctx_lens)
    k1, k2, k3 = jax.random.split(key, 3)
    log_a = -jnp.abs(jax.random.normal(k1, (b, s, w))) * 0.5
    gx = jax.random.normal(k2, (b, s, w))
    h0 = jax.random.normal(k3, (b, w))
    pos = _scan_pos(ctx_lens, s)
    h_k, hl_k = rglru_scan_pallas(log_a, gx, h0, pos, scan_dtype=scan_dtype,
                                  token_tile=tt, width_tile=wt, interpret=True)
    h_r, hl_r = ref.rglru_scan(log_a, gx, h0, pos, scan_dtype=scan_dtype)
    tol = 3e-2 if scan_dtype == jnp.bfloat16 else 1e-5
    _assert_close(h_k, h_r, tol)
    _assert_close(hl_k, hl_r, tol)
    # idle rows keep their carried state bitwise (f32 h_last path)
    for i, c in enumerate(ctx_lens):
        if c == 0:
            np.testing.assert_array_equal(np.asarray(hl_k[i]), np.asarray(h0[i]))
            np.testing.assert_array_equal(np.asarray(hl_r[i]), np.asarray(h0[i]))


def test_rglru_scan_no_positions_matches_masked_all_real(key):
    """pos=None (training path) must equal an all-real position grid."""
    b, s, w = 2, 8, 16
    k1, k2, k3 = jax.random.split(key, 3)
    log_a = -jnp.abs(jax.random.normal(k1, (b, s, w))) * 0.5
    gx = jax.random.normal(k2, (b, s, w))
    h0 = jax.random.normal(k3, (b, w))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h_n, hl_n = rglru_scan_pallas(log_a, gx, h0, None, interpret=True)
    h_p, hl_p = rglru_scan_pallas(log_a, gx, h0, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(h_n), np.asarray(h_p), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(hl_n), np.asarray(hl_p), rtol=1e-6, atol=1e-6)


def test_rglru_scan_decode_step_parity(key):
    """Fused masked decode step (S == 1): active rows advance, inactive rows
    keep their state bitwise, vs the oracle."""
    b, w = 4, 24
    k1, k2, k3 = jax.random.split(key, 3)
    log_a = -jnp.abs(jax.random.normal(k1, (b, 1, w))) * 0.5
    gx = jax.random.normal(k2, (b, 1, w))
    h0 = jax.random.normal(k3, (b, w))
    pos = jnp.asarray([[5], [-1], [0], [-1]], jnp.int32)
    h_k, hl_k = rglru_scan_pallas(log_a, gx, h0, pos, width_tile=16,
                                  interpret=True)
    h_r, hl_r = ref.rglru_scan(log_a, gx, h0, pos)
    _assert_close(h_k, h_r, 1e-6)
    _assert_close(hl_k, hl_r, 1e-6)
    for i in (1, 3):  # inactive slots: bitwise passthrough
        np.testing.assert_array_equal(np.asarray(hl_k[i]), np.asarray(h0[i]))


@pytest.mark.parametrize("s,h,hd,ctx_lens,chunk,int8", [
    (16, 2, 8, (16, 7, 0), 16, False),    # one exact chunk + ragged + idle
    (20, 2, 8, (20, 3), 16, False),       # ragged tail pads to 2 chunks
    (5, 1, 16, (5, 0), 16, False),        # prompt shorter than one chunk
    (24, 3, 8, (24, 11, 2), 8, False),    # narrow chunk, three slots
    (16, 2, 8, (16, 5, 0), 16, True),     # int8 state round-trip
    (9, 2, 16, (9, 1), 8, True),          # int8 + ragged pad
])
def test_wkv_scan_prefill_parity(s, h, hd, ctx_lens, chunk, int8, key):
    """Chunked wkv prefill kernel vs the masked oracle across chunk widths ×
    ragged/idle rows × f32/int8 state."""
    b = len(ctx_lens)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, hd)) * 2 - 1) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (h, hd)) * 0.1
    pos = _scan_pos(ctx_lens, s)
    if int8:
        s0f = jax.random.normal(key, (b, h, hd, hd)) * 0.3
        s0, sc0 = ref.quantize_state(s0f)
    else:
        s0 = jax.random.normal(key, (b, h, hd, hd)) * 0.3
        sc0 = None
    y_k, st_k, sc_k = wkv_scan_pallas(r, k, v, w, u, s0, pos, state_scale=sc0,
                                      chunk=chunk, interpret=True)
    y_r, st_r, sc_r = ref.wkv_scan(r, k, v, w, u, s0, pos, state_scale=sc0,
                                   chunk=chunk)
    _assert_close(y_k, y_r, 1e-5)
    if int8:
        # compare dequantized states; quantization boundaries may flip one
        # int8 step where the f32 values straddle a rounding edge
        d_k = np.asarray(st_k, np.float32) * np.asarray(sc_k)[..., None, None]
        d_r = np.asarray(st_r, np.float32) * np.asarray(sc_r)[..., None, None]
        atol = 2.0 * float(np.max(np.asarray(sc_r)))
        np.testing.assert_allclose(d_k, d_r, atol=atol)
        for i, c in enumerate(ctx_lens):
            if c == 0:  # idle rows: int8 payload AND scale bitwise-preserved
                np.testing.assert_array_equal(np.asarray(st_k[i]), np.asarray(s0[i]))
                np.testing.assert_array_equal(np.asarray(sc_k[i]), np.asarray(sc0[i]))
    else:
        assert sc_k is None and sc_r is None
        _assert_close(st_k, st_r, 1e-5)


def test_wkv_scan_decode_step_parity(key):
    """Fused masked decode step (S == 1) vs the sequential oracle, f32 and
    int8 state, with inactive slots bitwise-preserving payload and scale."""
    b, h, hd = 3, 2, 8
    ks = jax.random.split(key, 5)
    shape = (b, 1, h, hd)
    r = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    v = jax.random.normal(ks[2], shape)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], shape)) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (h, hd)) * 0.1
    pos = jnp.asarray([[4], [-1], [0]], jnp.int32)
    s0f = jax.random.normal(key, (b, h, hd, hd)) * 0.3
    y_k, st_k, _ = wkv_scan_pallas(r, k, v, w, u, s0f, pos, interpret=True)
    y_r, st_r, _ = ref.wkv_scan(r, k, v, w, u, s0f, pos)
    _assert_close(y_k, y_r, 1e-6)
    _assert_close(st_k, st_r, 1e-6)

    q0, sc0 = ref.quantize_state(s0f)
    yq, stq, scq = wkv_scan_pallas(r, k, v, w, u, q0, pos, state_scale=sc0,
                                   interpret=True)
    assert stq.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(stq[1]), np.asarray(q0[1]))
    np.testing.assert_array_equal(np.asarray(scq[1]), np.asarray(sc0[1]))


def test_scan_dispatch_backends(key):
    """ref and pallas-interpret agree through dispatch.rglru_scan /
    dispatch.wkv_scan (the policy chain the serve engine pins), and the
    dispatch-layer shape/scale validation raises."""
    b, s, w = 2, 8, 16
    k1, k2, k3 = jax.random.split(key, 3)
    log_a = -jnp.abs(jax.random.normal(k1, (b, s, w))) * 0.5
    gx = jax.random.normal(k2, (b, s, w))
    h0 = jax.random.normal(k3, (b, w))
    pos = _scan_pos((8, 3), s)
    h_ref, hl_ref = dispatch.rglru_scan(log_a, gx, h0, pos, backend="ref")
    h_pl, hl_pl = dispatch.rglru_scan(log_a, gx, h0, pos,
                                      backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl_pl), np.asarray(hl_ref),
                               rtol=1e-5, atol=1e-5)

    h2, hd = 2, 8
    ks = jax.random.split(key, 5)
    shape = (b, s, h2, hd)
    r = jax.random.normal(ks[0], shape)
    kk = jax.random.normal(ks[1], shape)
    v = jax.random.normal(ks[2], shape)
    ww = jax.nn.sigmoid(jax.random.normal(ks[3], shape)) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (h2, hd)) * 0.1
    s0 = jax.random.normal(key, (b, h2, hd, hd)) * 0.3
    y_ref, st_ref, _ = dispatch.wkv_scan(r, kk, v, ww, u, s0, pos, backend="ref")
    y_pl, st_pl, _ = dispatch.wkv_scan(r, kk, v, ww, u, s0, pos,
                                       backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_pl), np.asarray(st_ref),
                               rtol=1e-5, atol=1e-5)

    with pytest.raises(ValueError, match="log_a/gx"):
        dispatch.rglru_scan(log_a, gx[:, :-1], h0, backend="ref")
    with pytest.raises(ValueError, match="h0 must be"):
        dispatch.rglru_scan(log_a, gx, h0[:, :-1], backend="ref")
    with pytest.raises(ValueError, match="share one"):
        dispatch.wkv_scan(r, kk[:, :-1], v, ww, u, s0, backend="ref")
    with pytest.raises(ValueError, match="state0 must be"):
        dispatch.wkv_scan(r, kk, v, ww, u, s0[:, :, :-1], backend="ref")
    with pytest.raises(ValueError, match="state_scale"):
        dispatch.wkv_scan(r, kk, v, ww, u, s0.astype(jnp.int8), backend="ref")
    with pytest.raises(ValueError, match="state_scale"):
        dispatch.wkv_scan(r, kk, v, ww, u, s0,
                          state_scale=jnp.ones((b, h2)), backend="ref")


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "rwkv6-7b"])
def test_recurrent_session_parity_ref_vs_interpret(arch):
    """End-to-end: a full multi-layer recurrent session (griffin / rwkv)
    produces matching prefill AND decode logits under ref and
    pallas-interpret — the exact programs serve.steps jits for the engine."""
    from repro.configs import get_config
    from repro.kernels.dispatch import backend_override
    from repro.models import build_model
    from repro.models.sessions import SessionSpec, make_session

    cfg = get_config(arch, reduced=True).replace(
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = SessionSpec(slots=2, max_len=32, prefill_chunk=8, block_size=4)
    session = make_session(cfg, spec)
    rng = np.random.default_rng(9)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    pos = np.full((2, 8), -1, np.int32)
    pos[0, :8] = np.arange(8)
    pos[1, :3] = np.arange(3)  # ragged second row
    pos = jnp.asarray(pos)
    dt = jnp.asarray([[7], [11]], jnp.int32)
    dp = jnp.asarray([8, 3], jnp.int32)
    outs = {}
    for kb in ("ref", "pallas-interpret"):
        state = session.init_state()
        with backend_override(kb):
            plog, state = session.prefill_chunk(params, state, toks, pos)
            dlog, _ = session.decode_step(params, state, dt, dp)
        outs[kb] = (np.asarray(plog), np.asarray(dlog))
    np.testing.assert_allclose(outs["pallas-interpret"][0], outs["ref"][0],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(outs["pallas-interpret"][1], outs["ref"][1],
                               rtol=2e-4, atol=2e-4)
