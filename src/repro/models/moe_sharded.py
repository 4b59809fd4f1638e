"""Distribution-aware MoE dispatch (the production path).

Pure-GSPMD scatter dispatch replicates the [E*C, d] staging buffer on every
device (measured: 148 GB/chip on deepseek-v2 train_4k). The fix is the
standard production pattern — make dispatch LOCAL and exchange only the
expert-parallel payload:

- ``ep_a2a``  (E % model == 0, tokens shardable over data x model):
    shard_map manual over (data, model). Each device scatters its local
    tokens into a [E, C_loc, d] buffer, all_to_alls over the model axis so
    each rank holds its E/model experts' tokens from every peer, runs the
    batched expert FFN (weights FSDP-gathered over data manually), and
    all_to_alls back.

- ``local``   (experts not divisible by model — mixtral's 8 x 16 mesh):
    shard_map manual over data only; dispatch is local per data shard;
    expert FFN stays GSPMD-auto with per-expert TP over the ffn dim.

Routing (router matmul, softmax, top_k, aux loss) happens OUTSIDE the
manual region under plain GSPMD: it is tiny, and keeping bf16 replicated
weights out of the shard_map transpose sidesteps an XLA SPMD crash
("Invalid binary instruction opcode copy") hit when a bf16 cotangent is
psum'd back to a replicated shard_map input.

Falls back to the pure-GSPMD gather path when the batch can't shard
(long_500k B=1) or no mesh is active (CPU tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.base import silu


def _route(cfg, p, x2d):
    """Top-k routing + aux under plain GSPMD. x2d [T, d] (any sharding)."""
    m = cfg.moe
    logits = jnp.einsum(
        "td,de->te", x2d.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    E = m.num_experts
    onehot = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    return gates, ids, aux


def _dispatch_local(cfg, x2d, gates, ids, C):
    """Scatter local tokens into [E, C, d] + bookkeeping for combine."""
    m = cfg.moe
    T, d = x2d.shape
    E, k = m.num_experts, m.top_k
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    token_idx = jnp.repeat(jnp.arange(T), k)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    keep = pos < C
    dest = jnp.where(keep, flat_ids * C + pos, E * C)
    buf = jnp.zeros((E * C + 1, d), x2d.dtype).at[dest].add(x2d[token_idx])
    return buf[: E * C].reshape(E, C, d), dest, token_idx, flat_gates, keep


def _combine_local(yb, dest, token_idx, flat_gates, keep, T, d, dtype):
    yb_flat = jnp.concatenate([yb.reshape(-1, d), jnp.zeros((1, d), yb.dtype)])
    contrib = yb_flat[dest] * (flat_gates * keep)[:, None].astype(yb.dtype)
    out = jnp.zeros((T, d), yb.dtype).at[token_idx].add(contrib)
    return out.astype(dtype)


def _expert_ffn(w_gate, w_up, w_down, xb):
    h = jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(xb.dtype))
    u = jnp.einsum("ecd,edf->ecf", xb, w_up.astype(xb.dtype))
    return jnp.einsum("ecf,efd->ecd", silu(h) * u, w_down.astype(xb.dtype))


def moe_forward_ep_a2a(cfg, p, x):
    """x [B, S, d]; B%data==0, S%model==0, E%model==0. Returns (y, aux)."""
    m = cfg.moe
    B, S, d = x.shape
    E = m.num_experts
    n_model = cfg.act_shard_model

    x2d = x.reshape(B * S, d)
    gates, ids, aux = _route(cfg, p, x2d)
    gates = gates.reshape(B, S, m.top_k)
    ids = ids.reshape(B, S, m.top_k)

    wdt = jnp.dtype(cfg.dtype)

    def local(wg, wu, wd, x_loc, gates_loc, ids_loc):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        xl = x_loc.reshape(T, d)
        C = max(int(m.capacity_factor * m.top_k * T / E), 1)
        buf, dest, token_idx, fg, keep = _dispatch_local(
            cfg, xl, gates_loc.reshape(T, -1), ids_loc.reshape(T, -1), C
        )
        # EP exchange (tiled a2a): [E, C, d] -> [E/nm, nm*C, d]
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)
        # FSDP: gather the d-shard of the local expert weights over data.
        # weights stay f32 through the gather: ANY bf16 reduction at/inside
        # the shard_map transpose (psum or reduce-scatter) crashes this
        # XLA's SPMD partitioner; the cast to compute dtype happens after,
        # so the backward reduce-scatter runs in f32.
        wg_f = jax.lax.all_gather(wg, "data", axis=1, tiled=True).astype(wdt)
        wu_f = jax.lax.all_gather(wu, "data", axis=1, tiled=True).astype(wdt)
        wd_f = jax.lax.all_gather(wd, "data", axis=2, tiled=True).astype(wdt)
        yb = _expert_ffn(wg_f, wu_f, wd_f, buf)
        yb = jax.lax.all_to_all(yb, "model", split_axis=1, concat_axis=0, tiled=True)
        out = _combine_local(yb, dest, token_idx, fg, keep, T, d, x_loc.dtype)
        return out.reshape(Bl, Sl, d)

    mesh = jax.sharding.get_abstract_mesh()
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", "data", None), P("model", "data", None),
                  P("model", None, "data"), P("data", "model", None),
                  P("data", "model", None), P("data", "model", None)),
        out_specs=P("data", "model", None),
        axis_names=frozenset({"data", "model"}),
        check_vma=False,
    )
    y = fn(
        p["w_gate"].astype(jnp.float32),
        p["w_up"].astype(jnp.float32),
        p["w_down"].astype(jnp.float32),
        x, gates, ids,
    )
    return y, aux


def moe_forward_local(cfg, p, x):
    """Manual over data only; expert FFN under GSPMD TP (mixtral: E=8<16)."""
    m = cfg.moe
    B, S, d = x.shape
    E = m.num_experts

    x2d = x.reshape(B * S, d)
    gates, ids, aux = _route(cfg, p, x2d)
    gates = gates.reshape(B, S, m.top_k)
    ids = ids.reshape(B, S, m.top_k)

    wdt = jnp.dtype(cfg.dtype)

    def local(wg, wu, wd, x_loc, gates_loc, ids_loc):
        Bl = x_loc.shape[0]
        T = Bl * S
        xl = x_loc.reshape(T, d)
        C = max(int(m.capacity_factor * m.top_k * T / E), 1)
        buf, dest, token_idx, fg, keep = _dispatch_local(
            cfg, xl, gates_loc.reshape(T, -1), ids_loc.reshape(T, -1), C
        )
        # weights stay f32 through the gather (see ep_a2a note)
        wg_f = jax.lax.all_gather(wg, "data", axis=1, tiled=True).astype(wdt)
        wu_f = jax.lax.all_gather(wu, "data", axis=1, tiled=True).astype(wdt)
        wd_f = jax.lax.all_gather(wd, "data", axis=2, tiled=True).astype(wdt)
        yb = _expert_ffn(wg_f, wu_f, wd_f, buf)
        out = _combine_local(yb, dest, token_idx, fg, keep, T, d, x_loc.dtype)
        return out.reshape(Bl, S, d)

    mesh = jax.sharding.get_abstract_mesh()
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, "data", None), P(None, "data", None),
                  P(None, None, "data"), P("data", None, None),
                  P("data", None, None), P("data", None, None)),
        out_specs=P("data", None, None),
        axis_names=frozenset({"data"}),
        check_vma=False,
    )
    y = fn(
        p["w_gate"].astype(jnp.float32),
        p["w_up"].astype(jnp.float32),
        p["w_down"].astype(jnp.float32),
        x, gates, ids,
    )
    return y, aux


def moe_forward_ep_local(cfg, p, x):
    """Expert-parallel path for short sequences (decode): tokens replicated
    over model, each model rank dispatches ONLY its owned E/nm experts and
    the combined outputs psum (f32) over model. No a2a needed because every
    rank already sees all of its data-shard's tokens.
    """
    m = cfg.moe
    B, S, d = x.shape
    E = m.num_experts
    nm = cfg.act_shard_model
    E_loc = E // nm
    wdt = jnp.dtype(cfg.dtype)

    x2d = x.reshape(B * S, d)
    gates, ids, aux = _route(cfg, p, x2d)
    gates = gates.reshape(B, S, m.top_k)
    ids = ids.reshape(B, S, m.top_k)

    def local(wg, wu, wd, x_loc, gates_loc, ids_loc):
        Bl = x_loc.shape[0]
        T = Bl * S
        xl = x_loc.reshape(T, d)
        e0 = jax.lax.axis_index("model") * E_loc
        rel_ids = ids_loc.reshape(T, -1) - e0  # my experts: [0, E_loc)
        gl = gates_loc.reshape(T, -1)
        C = max(int(m.capacity_factor * m.top_k * T / E), 1)
        # dispatch only my experts; foreign tokens overflow to the waste row
        flat_ids = rel_ids.reshape(-1)
        flat_gates = gl.reshape(-1)
        token_idx = jnp.repeat(jnp.arange(T), m.top_k)
        mine = (flat_ids >= 0) & (flat_ids < E_loc)
        safe_ids = jnp.clip(flat_ids, 0, E_loc - 1)
        onehot = jax.nn.one_hot(safe_ids, E_loc, dtype=jnp.int32) * mine[:, None]
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
        keep = mine & (pos < C)
        dest = jnp.where(keep, safe_ids * C + pos, E_loc * C)
        buf = jnp.zeros((E_loc * C + 1, d), xl.dtype).at[dest].add(xl[token_idx])
        buf = buf[: E_loc * C].reshape(E_loc, C, d)

        wg_f = jax.lax.all_gather(wg, "data", axis=1, tiled=True).astype(wdt)
        wu_f = jax.lax.all_gather(wu, "data", axis=1, tiled=True).astype(wdt)
        wd_f = jax.lax.all_gather(wd, "data", axis=2, tiled=True).astype(wdt)
        yb = _expert_ffn(wg_f, wu_f, wd_f, buf)
        out = _combine_local(yb, dest, token_idx, flat_gates, keep, T, d, jnp.float32)
        out = jax.lax.psum(out, "model")  # f32: bf16 psum crashes (see above)
        return out.astype(x_loc.dtype).reshape(Bl, S, d)

    mesh = jax.sharding.get_abstract_mesh()
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", "data", None), P("model", "data", None),
                  P("model", None, "data"), P("data", None, None),
                  P("data", None, None), P("data", None, None)),
        out_specs=P("data", None, None),
        axis_names=frozenset({"data", "model"}),
        check_vma=False,
    )
    y = fn(
        p["w_gate"].astype(jnp.float32),
        p["w_up"].astype(jnp.float32),
        p["w_down"].astype(jnp.float32),
        x, gates, ids,
    )
    return y, aux


def pick_moe_path(cfg, B: int, S: int) -> str:
    """Select the dispatch implementation for this shape/mesh."""
    m = cfg.moe
    nd, nm = cfg.act_shard_data, cfg.act_shard_model
    if m.impl in ("gather", "einsum"):
        return m.impl
    if not nd or B % nd != 0 or cfg.d_model % nd != 0:
        return "gather"  # no mesh (CPU tests) or unshardable batch (B=1)
    if nm and m.num_experts % nm == 0:
        if S % nm == 0:
            return "ep_a2a"  # train/prefill: tokens shard over model too
        return "ep_local"  # decode: tokens replicated over model, owned experts
    return "local"  # experts don't divide model (mixtral): ffn-TP under auto
