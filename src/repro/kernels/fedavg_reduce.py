"""Pallas kernel: fused weighted FedAvg reduction.

The server-side aggregation hot spot: out = sum_c w[c] * X[c, :] over C
stacked client deltas. Done naively (tree_weighted_mean) XLA materializes
per-client scaled copies; the kernel streams X through VMEM tile by tile
and keeps a single f32 accumulator — one pass, no intermediates.

Grid: (n_tiles,) over the flattened parameter axis; weights stay resident.
The weighted sum runs on the vector unit in f32: a [1, C] x [C, T]
product leaves the matrix unit idle and would round its operands to its
default input precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Elements of X per block: the tile narrows as C grows, so a block stays
# near 2 MiB of f32 at any cohort width.
_BLOCK_ELEMS = 512 * 1024


def _reduce_kernel(w_ref, x_ref, o_ref):
    # w_ref [C, 1] f32; x_ref [C, T]; o_ref [1, T]
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(w_ref[...] * x, axis=0, keepdims=True)


def fedavg_reduce_flat(x, w, *, tile: int = 2048, interpret: bool = False):
    """x [C, N], w [C] (already normalized) -> [N] weighted sum.

    Blocks span all C rows; columns are ``tile`` wide (narrowed to keep a
    block near ``_BLOCK_ELEMS``), or the whole row when it is narrower.
    The last block may overhang N: its out-of-range writes are dropped.
    """
    C, N = x.shape
    tile = max(128, min(tile, _BLOCK_ELEMS // C) // 128 * 128)
    bn = N if N <= tile else tile
    out = pl.pallas_call(
        _reduce_kernel,
        grid=(pl.cdiv(N, bn),),
        in_specs=[
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
            pl.BlockSpec((C, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(w.reshape(C, 1).astype(jnp.float32), x)
    return out[0]
