"""Sharded step builders: train_step / prefill_step / decode_step /
outer_sync per (arch config, shape, mesh).

Two multi-pod modes (DESIGN §3):

- ``sync``       — plain synchronous DP: one jit over the full mesh, grads
                   all-reduce over (pod, data).
- ``local_sgd``  — the paper-faithful federated mode: shard_map manual over
                   "pod" (each pod = an FL client running H inner steps on
                   its own replica), GSPMD auto over (data, model) inside;
                   ``outer_sync`` is the FedAvg burst over the slow
                   cross-pod link, optionally int8/top-k compressed (the
                   gradient-compression trick made visible in the HLO).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec, TrainConfig
from repro.models import Model
from repro.optim import adamw, apply_updates, clip_by_global_norm, cosine_warmup
from repro.sharding import (
    batch_spec,
    cache_shardings,
    input_shardings,
    param_shardings,
)


@dataclass
class BuiltStep:
    """A lowered-able step: fn + abstract args + shardings, ready for
    jit(...).lower(*abstract_args)."""

    name: str
    fn: Callable
    abstract_args: Tuple
    in_shardings: Tuple
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()


def _with_act_sharding(cfg: ModelConfig, mesh: Mesh) -> ModelConfig:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return cfg.replace(
        act_shard_data=sizes.get("data", 0), act_shard_model=sizes.get("model", 0)
    )


def _mirror_state_shardings(state_abs, params_treedef, p_shardings, mesh,
                            abstract_params=None):
    """Optimizer-state shardings: trees mirroring params inherit the param
    shardings; adafactor's factored moments inherit the matching reduced
    specs (vr drops the last param dim, vc the second-to-last); everything
    else is replicated."""
    rep = NamedSharding(mesh, P())

    def _is_factored(sub):
        leaves = jax.tree.leaves(sub, is_leaf=lambda x: isinstance(x, dict) and ("vr" in x or "v" in x))
        return leaves and all(isinstance(l, dict) for l in leaves)

    def build(sub):
        if jax.tree.structure(sub) == params_treedef:
            return p_shardings
        if abstract_params is not None and _is_factored(sub):
            def fact(ap, sh, vd):
                spec = list(sh.spec) + [None] * (len(ap.shape) - len(sh.spec))
                if "v" in vd:
                    return {"v": NamedSharding(mesh, P(*spec))}
                return {
                    "vr": NamedSharding(mesh, P(*spec[:-1])),
                    "vc": NamedSharding(mesh, P(*(spec[:-2] + [spec[-1]]))),
                }

            return jax.tree.map(
                fact, abstract_params, p_shardings, sub,
                is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict),
            )
        return jax.tree.map(lambda _: rep, sub)

    return {k: build(v) for k, v in state_abs.items()}


def make_optimizer(tcfg: TrainConfig):
    lr = cosine_warmup(tcfg.learning_rate, tcfg.warmup_steps, tcfg.total_steps)
    if tcfg.optimizer == "adafactor":
        # the production choice at the 236B tier (T5/PaLM-style): factored
        # second moments, no first moment, no master copy — state bytes and
        # update-pipeline temporaries shrink by ~7x vs AdamW
        from repro.optim import adafactor

        return adafactor(lr)
    state_dtype = jnp.dtype(tcfg.opt_state_dtype)
    master = jnp.float32 if tcfg.opt_state_dtype != "float32" else None
    return adamw(
        lr, tcfg.beta1, tcfg.beta2, tcfg.eps, tcfg.weight_decay,
        state_dtype=state_dtype, master_dtype=master,
    )


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    shape: ShapeSpec,
    mesh: Mesh,
    *,
    mode: str = "sync",  # sync | local_sgd (multi-pod only)
) -> BuiltStep:
    cfg = _with_act_sharding(cfg, mesh)
    model = Model(cfg)
    opt = make_optimizer(tcfg)
    multi_pod = "pod" in mesh.axis_names

    abstract_params = model.abstract_params()
    axes = model.param_axes()
    p_shard = param_shardings(abstract_params, axes, mesh)
    state_abs = jax.eval_shape(opt.init, abstract_params)
    s_shard = _mirror_state_shardings(
        state_abs, jax.tree.structure(abstract_params), p_shard, mesh,
        abstract_params=abstract_params,
    )
    inputs_abs = model.input_specs(shape)
    in_shard = input_shardings(inputs_abs, mesh, include_pod=(mode == "sync"))
    rep = NamedSharding(mesh, P())

    n_micro = max(tcfg.microbatches, 1)
    local_sgd = multi_pod and mode == "local_sgd"
    mb_spec = batch_spec(mesh, shape.global_batch // n_micro,
                         include_pod=not local_sgd)

    def train_step(train_state, batch):
        params, opt_state, step = (
            train_state["params"],
            train_state["opt"],
            train_state["step"],
        )

        def loss_and_grads(b):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: model.loss(p, b), has_aux=True
            )(params)
            return metrics, grads

        if n_micro == 1:
            metrics, grads = loss_and_grads(batch)
        else:
            # gradient accumulation: first microbatch inline (fixes the
            # carry structure), remaining n-1 under lax.scan with an f32
            # accumulator sharded like the params — the activation-memory
            # lever that keeps remat="block" affordable at 64k tokens/chip.
            def reshape_mb(x):
                y = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
                return jax.lax.with_sharding_constraint(
                    y, P(None, mb_spec, *([None] * (x.ndim - 1)))
                )

            mb = jax.tree.map(reshape_mb, batch)
            m0, g0 = loss_and_grads(jax.tree.map(lambda x: x[0], mb))
            g0 = jax.tree.map(lambda g: g.astype(jnp.float32), g0)
            m0 = jax.tree.map(lambda m: m.astype(jnp.float32), m0)

            def micro(carry, b):
                gsum, msum = carry
                metrics, grads = loss_and_grads(b)
                gsum = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads
                )
                msum = jax.tree.map(
                    lambda a, m: a + m.astype(jnp.float32), msum, metrics
                )
                return (gsum, msum), None

            rest = jax.tree.map(lambda x: x[1:], mb)
            (gsum, msum), _ = jax.lax.scan(micro, (g0, m0), rest)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            metrics = jax.tree.map(lambda m: m / n_micro, msum)

        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        updates, opt_state = opt.update(grads, opt_state, params, step)
        params = apply_updates(params, updates)
        metrics = dict(metrics, grad_norm=gnorm)
        return {"params": params, "opt": opt_state, "step": step + 1}, metrics

    state_shardings = {"params": p_shard, "opt": s_shard, "step": rep}
    state_abs_full = {
        "params": abstract_params,
        "opt": state_abs,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    with jax.set_mesh(mesh):
        _, metrics_abs = jax.eval_shape(train_step, state_abs_full, inputs_abs)
    metrics_shard = jax.tree.map(lambda _: rep, metrics_abs)

    if local_sgd:
        # Per-pod replicas via vmap(spmd_axis_name="pod"): every leaf gets a
        # leading pod dim sharded over "pod", the pods train independently
        # (no cross-pod collectives in train_step — the FL semantics), and
        # sharding constraints inside the model are pod-prefixed
        # automatically. This avoids nesting GSPMD inside a manual
        # shard_map region, which this XLA build miscompiles (DESIGN §10.6).
        n_pod = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]

        def stack(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct((n_pod,) + a.shape, a.dtype), tree
            )

        def shard_stack(tree):
            return jax.tree.map(
                lambda s: NamedSharding(s.mesh, P(*(("pod",) + tuple(s.spec)))), tree
            )

        fn = jax.vmap(train_step, spmd_axis_name="pod")
        state_abs_full = stack(state_abs_full)
        state_shardings = shard_stack(state_shardings)
        inputs_abs = {
            k: jax.ShapeDtypeStruct(
                (n_pod, v.shape[0] // n_pod) + v.shape[1:], v.dtype
            )
            for k, v in inputs_abs.items()
        }
        in_shard = shard_stack(in_shard)
        metrics_shard = shard_stack(metrics_shard)
    else:
        fn = train_step

    return BuiltStep(
        name=f"train:{cfg.name}:{shape.name}:{mode}",
        fn=fn,
        abstract_args=(state_abs_full, inputs_abs),
        in_shardings=(state_shardings, in_shard),
        out_shardings=(state_shardings, metrics_shard),
        donate_argnums=(0,),
    )


# ---------------------------------------------------------------------------
# Outer sync (FedAvg across pods over the constrained link)
# ---------------------------------------------------------------------------


def build_outer_sync(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh: Mesh,
    *,
    compression: Optional[str] = None,
) -> BuiltStep:
    """Cross-pod FedAvg burst on pod-stacked replicas: delta = params[p] -
    anchor, averaged over the pod dim (optionally int8 on the wire), outer
    Nesterov step on the anchor, replicas reset to the new anchor. This is
    the FL round's model-update burst in datacenter form — the pod-dim mean
    lowers to cross-pod all-reduce/all-gather collectives (visible in the
    HLO, recorded in the dry-run).
    """
    assert "pod" in mesh.axis_names, "outer sync requires the multi-pod mesh"
    compression = compression or tcfg.compression
    model = Model(cfg)
    abstract_params = model.abstract_params()
    axes = model.param_axes()
    p_shard = param_shardings(abstract_params, axes, mesh)
    rep = NamedSharding(mesh, P())
    n_pod = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]

    stacked_abs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n_pod,) + a.shape, a.dtype), abstract_params
    )
    stacked_shard = jax.tree.map(
        lambda s: NamedSharding(s.mesh, P(*(("pod",) + tuple(s.spec)))), p_shard
    )

    from repro.optim import nesterov_outer

    outer = nesterov_outer(tcfg.outer_lr, tcfg.outer_momentum)
    outer_abs = jax.eval_shape(outer.init, abstract_params)
    o_shard = _mirror_state_shardings(
        outer_abs, jax.tree.structure(abstract_params), p_shard, mesh,
        abstract_params=abstract_params,
    )

    def sync(params_stacked, anchor, outer_state, step):
        def avg_delta(ps, a, sh):
            d = ps.astype(jnp.float32) - a.astype(jnp.float32)[None]
            if compression == "int8":
                # per-pod int8 quantization; replicating the int8 tensor over
                # the pod axis (not the f32 one) puts the compressed payload
                # on the cross-pod wire
                scale = jnp.maximum(
                    jnp.max(jnp.abs(d), axis=tuple(range(1, d.ndim)), keepdims=True),
                    1e-12,
                ) / 127.0
                q = jnp.clip(jnp.round(d / scale), -127, 127).astype(jnp.int8)
                q = jax.lax.with_sharding_constraint(
                    q, NamedSharding(mesh, P(*((None,) + tuple(sh.spec))))
                )
                d = q.astype(jnp.float32) * scale
            return jnp.mean(d, axis=0)

        delta = jax.tree.map(avg_delta, params_stacked, anchor, p_shard)
        upd, outer_state = outer.update(delta, outer_state, anchor, step)
        new_anchor = jax.tree.map(
            lambda a, u: (a.astype(jnp.float32) + u).astype(a.dtype), anchor, upd
        )
        new_stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_pod,) + a.shape), new_anchor
        )
        return new_stacked, new_anchor, outer_state

    step_abs = jax.ShapeDtypeStruct((), jnp.int32)
    return BuiltStep(
        name=f"outer_sync:{cfg.name}:{compression}",
        fn=sync,
        abstract_args=(stacked_abs, abstract_params, outer_abs, step_abs),
        in_shardings=(stacked_shard, p_shard, o_shard, rep),
        out_shardings=(stacked_shard, p_shard, o_shard),
        donate_argnums=(0, 1, 2),
    )


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh) -> BuiltStep:
    cfg = _with_act_sharding(cfg, mesh)
    model = Model(cfg)
    abstract_params = model.abstract_params()
    axes = model.param_axes()
    fsdp = cfg.param_count() > 1e10  # see build_decode_step
    p_shard = param_shardings(abstract_params, axes, mesh, fsdp=fsdp)
    inputs_abs = model.input_specs(shape)
    in_shard = input_shardings(inputs_abs, mesh)
    b_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    cache_abs = model.cache_spec(shape.global_batch, shape.seq_len)
    c_axes = model.cache_axes(shape.global_batch, shape.seq_len)
    c_shard = cache_shardings(cache_abs, c_axes, mesh, batch_axes=b_axes)
    rep = NamedSharding(mesh, P())

    def prefill(params, batch):
        logits, cache = model.prefill(params, batch, shape.seq_len)
        return logits, cache

    return BuiltStep(
        name=f"prefill:{cfg.name}:{shape.name}",
        fn=prefill,
        abstract_args=(abstract_params, inputs_abs),
        in_shardings=(p_shard, in_shard),
        out_shardings=(rep, c_shard),
    )


def build_decode_step(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh) -> BuiltStep:
    cfg = _with_act_sharding(cfg, mesh)
    model = Model(cfg)
    abstract_params = model.abstract_params()
    axes = model.param_axes()
    # >10B params: shard weights over data at serve time too (per-layer
    # gathers beat not fitting — deepseek 472GB, phi3-medium's replicated
    # non-divisible-head attention weights)
    fsdp = cfg.param_count() > 1e10
    p_shard = param_shardings(abstract_params, axes, mesh, fsdp=fsdp)
    b_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    cache_abs = model.cache_spec(shape.global_batch, shape.seq_len)
    c_axes = model.cache_axes(shape.global_batch, shape.seq_len)
    c_shard = cache_shardings(cache_abs, c_axes, mesh, batch_axes=b_axes)
    tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_shard = NamedSharding(
        mesh, P(batch_spec(mesh, shape.global_batch, include_pod=True), None)
    )
    rep = NamedSharding(mesh, P())

    def decode(params, cache, tokens):
        logits, new_cache = model.decode_step(params, cache, tokens)
        return logits, new_cache

    return BuiltStep(
        name=f"decode:{cfg.name}:{shape.name}",
        fn=decode,
        abstract_args=(abstract_params, cache_abs, tok_abs),
        in_shardings=(p_shard, c_shard, tok_shard),
        out_shardings=(rep, c_shard),
        donate_argnums=(1,),
    )


def build_step(cfg: ModelConfig, tcfg: TrainConfig, shape: ShapeSpec, mesh: Mesh,
               *, mode: str = "sync") -> BuiltStep:
    """Dispatch on the shape kind (train/prefill/decode)."""
    if shape.kind == "train":
        return build_train_step(cfg, tcfg, shape, mesh, mode=mode)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh)
    return build_decode_step(cfg, shape, mesh)
