"""The served path's Pallas kernels compile for a TPU v5e at chatglm3-6b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
for a v5e that the TPU compiler describes without one attached, which
refuses what the chip would refuse (tile rules, unsupported vector shape
casts, VMEM overflow).  The topology is described inside the module
fixture, never at import, so every test worker collects the same tests and
only the worker running this file loads the TPU library.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.chatglm3_6b import TT_OVERRIDES, config
from repro.core.ttd import TTSpec
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.prefill_attention import prefill_attention_pallas
from repro.kernels.tt_linear import tt_linear_pallas

SLOTS, CHUNK = 4, 256
PREFILL_BATCH = 2  # rows of a paged prefill tile: the admitted prompts
POOL_BLOCKS, BLOCK = 4096, 16  # 32 MiB of bf16 K per layer: no VMEM holds it
TABLE_WIDTH = 2048 // BLOCK


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not an interpreter


@pytest.mark.parametrize("rows", [8, 256, PREFILL_BATCH * CHUNK])
@pytest.mark.parametrize("role", [r for r, _ in TT_OVERRIDES])
def test_tt_linear_compiles_for_v5e(one_chip, role, rows):
    ov = dict(TT_OVERRIDES)[role]
    spec = TTSpec.make(math.prod(ov.in_modes), math.prod(ov.out_modes),
                       ov.rank, in_modes=ov.in_modes, out_modes=ov.out_modes)
    bf16 = jnp.bfloat16
    core_shapes = [(s, bf16) for s in spec.core_matrix_shapes()]

    def fn(x, res, *cores):
        return tt_linear_pallas(x, list(cores), spec, residual=res,
                                interpret=False)

    _compile(fn, ((rows, spec.n_in), bf16), ((rows, spec.n_out), bf16),
             *core_shapes, sharding=one_chip)


def _pool():
    cfg = config()
    shape = (POOL_BLOCKS, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    return cfg, (shape, jnp.bfloat16)


def test_paged_attention_compiles_for_v5e(one_chip):
    cfg, pool = _pool()

    def fn(q, k, v, bt, qpos):
        return paged_attention_pallas(q, {"k": k, "v": v}, bt, qpos,
                                      interpret=False)

    _compile(fn, ((SLOTS, cfg.n_heads, cfg.head_dim), jnp.bfloat16), pool, pool,
             ((SLOTS, TABLE_WIDTH), jnp.int32), ((SLOTS,), jnp.int32),
             sharding=one_chip)


def _compile_prefill_attention(rows, sharding):
    cfg, pool = _pool()

    def fn(q, qpos, k, v, bt):
        return prefill_attention_pallas(q, qpos, cache={"k": k, "v": v},
                                        block_tables=bt, interpret=False)

    _compile(fn, ((rows, CHUNK, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
             ((rows, CHUNK), jnp.int32), pool, pool,
             ((rows, TABLE_WIDTH), jnp.int32), sharding=sharding)


def test_prefill_attention_compiles_for_v5e(one_chip):
    _compile_prefill_attention(SLOTS, one_chip)


def test_prefill_attention_compiles_for_v5e_admitted_rows(one_chip):
    """The paged prefill tile: ``PREFILL_BATCH`` rows, one per admitted prompt."""
    _compile_prefill_attention(PREFILL_BATCH, one_chip)
