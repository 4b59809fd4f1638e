"""Pallas kernels: int8 / bf16 quantization for the compression front-end.

Two families serve the constrained link (repro.compress):

- ``quantize_stochastic_flat``: per-tensor int8 with stochastic rounding,
  q = clip(round_sr(x/scale)) where round_sr(y) = floor(y + u), u ~ U[0,1)
  supplied as precomputed uniform bits (keeps the kernel deterministic and
  oracle-checkable; on real TPU the bits would come from pltpu.prng_*).
- ``quantize_rows_flat`` / ``downcast_bf16_rows_flat``: ROW-STACKED int8 /
  bf16 for the plane-resident compressors — one row per (scenario, client)
  plane slot, per-row scales, deterministic round-half-up so the stacked
  path is bitwise identical to sequential per-client compression (the
  error-feedback residual makes any deterministic rounding unbiased over
  rounds).

Grids tile the flattened tensor(s); scales are computed by the caller —
the kernels are pure elementwise + cast, VMEM-tiled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Rows per block once the row axis no longer fits one block. Mosaic tiles
# the last two block dims by (8, 128) for f32 and (32, 128) for int8 unless
# a dim spans its whole array, so a row block is either all R rows or a
# multiple of 32.
_ROW_BLOCK = 32


def _quant_kernel(x_ref, u_ref, s_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    scale = s_ref[0, 0]
    y = x / scale
    q = jnp.floor(y + u)  # stochastic rounding
    q_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def quantize_stochastic_flat(x, uniform, scale, *, tile: int = 4096, interpret: bool = False):
    """x [N] f32, uniform [N] in [0,1), scale scalar -> int8 [N]."""
    (N,) = x.shape
    pad = (-N) % tile
    if pad:
        x = jnp.pad(x, (0, pad))
        uniform = jnp.pad(uniform, (0, pad))
    Np = x.shape[0]
    q = pl.pallas_call(
        _quant_kernel,
        grid=(Np // tile,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int8),
        interpret=interpret,
    )(x.reshape(1, Np), uniform.reshape(1, Np), jnp.reshape(scale, (1, 1)))
    return q[0, :N]


def dequantize_flat(q, scale):
    return q.astype(jnp.float32) * scale


def _row_blocks(R: int, N: int, tile: int):
    """(block shape, grid) for an elementwise kernel over rows [R, N].

    A block spans every row up to ``_ROW_BLOCK`` rows and the whole row up
    to ``tile`` columns; past that it is ``_ROW_BLOCK`` rows or ``tile``
    columns. Edge blocks that overhang R or N are partial: Pallas drops
    their out-of-range writes, and the op is elementwise, so no padding
    copy is made.
    """
    if tile % 128:
        raise ValueError(f"tile must be a multiple of 128, got {tile}")
    br = R if R <= _ROW_BLOCK else _ROW_BLOCK
    bn = N if N <= tile else tile
    return (br, bn), (pl.cdiv(R, br), pl.cdiv(N, bn))


def _quant_rows_kernel(x_ref, s_ref, q_ref):
    y = x_ref[...].astype(jnp.float32) / s_ref[...]  # [br, bn] / [br, 1]
    q = jnp.floor(y + 0.5)  # deterministic round-half-up (parity contract)
    q_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def quantize_rows_flat(x, scales, *, tile: int = 2048, interpret: bool = False):
    """x [R, N] f32, scales [R] (per-row quantum) -> int8 [R, N].

    Each block reads its rows' scales through an [rows, 1] block.
    Deterministic rounding: the plane compressors need the kernel output
    bitwise equal to the sequential per-client reference.
    """
    R, N = x.shape
    block, grid = _row_blocks(R, N, tile)
    return pl.pallas_call(
        _quant_rows_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(block, lambda r, i: (r, i)),
            pl.BlockSpec((block[0], 1), lambda r, i: (r, 0)),
        ],
        out_specs=pl.BlockSpec(block, lambda r, i: (r, i)),
        out_shape=jax.ShapeDtypeStruct((R, N), jnp.int8),
        interpret=interpret,
    )(x, scales.reshape(R, 1))


def dequantize_rows(q, scales):
    return q.astype(jnp.float32) * scales[:, None]


def _bf16_rows_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.float32).astype(jnp.bfloat16)


def downcast_bf16_rows_flat(x, *, tile: int = 2048, interpret: bool = False):
    """x [R, N] f32 -> bf16 [R, N] (round-to-nearest-even downcast)."""
    R, N = x.shape
    block, grid = _row_blocks(R, N, tile)
    return pl.pallas_call(
        _bf16_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda r, i: (r, i))],
        out_specs=pl.BlockSpec(block, lambda r, i: (r, i)),
        out_shape=jax.ShapeDtypeStruct((R, N), jnp.bfloat16),
        interpret=interpret,
    )(x)
