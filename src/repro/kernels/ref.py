"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q [BH, Sq, D]; k/v [BKV, Skv, D]; GQA via BH = G * BKV."""
    BH, Sq, D = q.shape
    BKV, Skv, Dv = v.shape
    G = BH // BKV
    scale = scale if scale is not None else D ** -0.5
    kr = jnp.repeat(k, G, axis=0)
    vr = jnp.repeat(v, G, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), kr.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window and window > 0:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, vr.astype(jnp.float32)).astype(q.dtype)


def fedavg_reduce_ref(x, w):
    """x [C, N], w [C] -> [N], at full f32 precision on every backend."""
    return jnp.einsum(
        "c,cn->n", w.astype(jnp.float32), x.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def quantize_stochastic_ref(x, uniform, scale):
    y = x.astype(jnp.float32) / scale
    return jnp.clip(jnp.floor(y + uniform), -127.0, 127.0).astype(jnp.int8)


def quantize_rows_ref(x, scales):
    """x [R, N], scales [R] -> int8 [R, N]; deterministic round-half-up."""
    y = x.astype(jnp.float32) / scales[:, None]
    return jnp.clip(jnp.floor(y + 0.5), -127.0, 127.0).astype(jnp.int8)


def downcast_bf16_rows_ref(x):
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def segment_sum_ref(values, segment_ids, num_segments):
    """values [K], segment_ids [K] int -> [num_segments] scatter-add."""
    out = jnp.zeros((num_segments,) + values.shape[1:], values.dtype)
    return out.at[segment_ids].add(values)


def swiglu_ref(x, w_gate, w_up, w_down):
    g = (x.astype(jnp.float32) @ w_gate.astype(jnp.float32))
    u = (x.astype(jnp.float32) @ w_up.astype(jnp.float32))
    h = g * jax.nn.sigmoid(g) * u
    return (h @ w_down.astype(jnp.float32)).astype(x.dtype)
