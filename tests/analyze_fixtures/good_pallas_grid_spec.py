"""Fixture: a grid carried by ``grid_spec=`` declares the grid (no PAL001)."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _k(idx_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def double_rows(x, idx):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(x.shape[0] // 8,),
        in_specs=[pl.BlockSpec((8, x.shape[1]), lambda i, idx: (idx[i], 0))],
        out_specs=pl.BlockSpec((8, x.shape[1]), lambda i, idx: (i, 0)))
    return pl.pallas_call(
        _k, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32))(idx, x)
