"""Pallas embedding-bag: gather-by-prefetched-id + in-VMEM reduce.

The recsys lookup hot path. Indices are scalar-prefetched so the BlockSpec
index map streams exactly the needed table rows HBM→VMEM; the bag reduction
accumulates in a VMEM scratch across the (sequential) bag-position grid dim.
Padding ids (< 0) contribute zero without branching (masked add).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _bag_kernel(ids_ref, row_ref, o_ref, acc_scr, *, bag_len: int, mode: str):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = (ids_ref[b, l] >= 0).astype(jnp.float32)
    acc_scr[...] += row_ref[...].astype(jnp.float32) * valid

    @pl.when(l == bag_len - 1)
    def _finalize():
        out = acc_scr[...]
        if mode == "mean":
            cnt = jnp.zeros((), jnp.float32)
            for i in range(bag_len):  # bag_len is static and small
                cnt += (ids_ref[b, i] >= 0).astype(jnp.float32)
            out = out / jnp.maximum(cnt, 1.0)
        o_ref[...] = out.astype(o_ref.dtype)


def embedding_bag(table: Array, idx: Array, *, mode: str = "sum",
                  interpret: bool = False) -> Array:
    """table (V, D); idx (B, L) int (-1 pads) -> (B, D) reduced bags."""
    assert mode in ("sum", "mean")
    v, d = table.shape
    b, L = idx.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, L),
        in_specs=[
            pl.BlockSpec((1, d), lambda bi, li, ids: (jnp.maximum(ids[bi, li], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda bi, li, ids: (bi, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bag_kernel, bag_len=L, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(idx.astype(jnp.int32), table)
