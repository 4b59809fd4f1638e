"""End-to-end trainer: local-update (FL-across-pods) training with
fault-tolerant checkpointing and elastic restart.

Modes:
- CPU/dev (default): reduced config, host mesh, REAL optimization on
  synthetic token data — used by examples/train_100m.py and tests.
- Production: full config on the production mesh; this script is the same
  code path the dry-run lowers (build_train_step/build_outer_sync), so a
  TPU deployment changes only ``--mesh prod``.

Fault tolerance: CheckpointManager writes atomic round-granular state; on
restart the trainer resumes from LATEST (crash-consistent). Elastic: state
is saved unsharded, so a restart may use a different mesh/pod count — the
in_shardings of the rebuilt step re-shard on load.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 50 --inner-steps 5 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import TrainConfig, get_config, get_reduced
from repro.configs.base import ShapeSpec
from repro.data.tokens import token_batch_for
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import build_outer_sync, build_train_step, make_optimizer
from repro.models import Model
from repro.utils import tree_sub


def train(
    arch: str,
    *,
    reduced: bool = True,
    steps: int = 50,
    inner_steps: int = 1,
    batch: int = 8,
    seq: int = 64,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 10,
    mesh_kind: str = "host",
    seed: int = 0,
    log_every: int = 10,
    outer_compression: str = "none",
    learning_rate: float = 2e-3,
):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    tcfg = TrainConfig(
        learning_rate=learning_rate,
        total_steps=steps,
        warmup_steps=max(steps // 10, 1),
        inner_steps=inner_steps,
        compression=outer_compression,
    )
    if mesh_kind == "prod":
        mesh = make_production_mesh(multi_pod=inner_steps > 1)
    else:
        mesh = make_host_mesh()

    shape = ShapeSpec("custom", "train", seq, batch)
    built = build_train_step(cfg, tcfg, shape, mesh)
    model = Model(cfg)
    opt = make_optimizer(tcfg)

    with jax.set_mesh(mesh):
        step_fn = jax.jit(
            built.fn,
            in_shardings=built.in_shardings,
            out_shardings=built.out_shardings,
            donate_argnums=built.donate_argnums,
        )

        params = model.init(jax.random.PRNGKey(seed))
        state = {
            "params": params,
            "opt": opt.init(params),
            "step": jnp.int32(0),
        }

        mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        start_step = 0
        if mgr is not None:
            restored = mgr.restore_latest(state)
            if restored is not None:
                state, meta = restored
                start_step = int(meta.get("step", 0))
                print(f"[train] resumed from checkpoint at step {start_step}")

        # local-SGD outer state (anchor = last synced params; COPIED — the
        # train step donates its input state, so aliasing would leave the
        # anchor pointing at deleted buffers)
        anchor = jax.tree.map(lambda x: jnp.array(x), state["params"])
        from repro.optim import nesterov_outer

        outer = nesterov_outer(tcfg.outer_lr, tcfg.outer_momentum)
        outer_state = outer.init(anchor)

        losses = []
        t0 = time.time()
        for it in range(start_step, steps):
            np_batch = token_batch_for(cfg, batch=batch, seq=seq, seed=seed + it)
            jbatch = {k: jnp.asarray(v) for k, v in np_batch.items()}
            state, metrics = step_fn(state, jbatch)
            losses.append(float(metrics["loss"]))

            if inner_steps > 1 and (it + 1) % inner_steps == 0:
                # outer FedAvg step (single-host: pod count 1 -> plain outer opt)
                delta = tree_sub(state["params"], anchor)
                upd, outer_state = outer.update(delta, outer_state, anchor, jnp.int32(it))
                new_anchor = jax.tree.map(
                    lambda a, u: (a.astype(jnp.float32) + u).astype(a.dtype), anchor, upd
                )
                # keep the anchor in buffers the (donating) step can't delete
                anchor = jax.tree.map(lambda x: jnp.array(x), new_anchor)
                state = dict(state, params=new_anchor)

            if mgr is not None and (it + 1) % ckpt_every == 0:
                mgr.save(it + 1, state, metadata={"arch": arch, "loss": losses[-1]})
            if (it + 1) % log_every == 0:
                dt = time.time() - t0
                print(
                    f"[train] step {it+1}/{steps} loss={losses[-1]:.4f} "
                    f"({dt/ (it + 1 - start_step):.2f}s/step)"
                )

        return {"losses": losses, "final_loss": losses[-1] if losses else float("nan")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--inner-steps", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--mesh", default="host", choices=["host", "prod"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    out = train(
        args.arch,
        reduced=args.reduced,
        steps=args.steps,
        inner_steps=args.inner_steps,
        batch=args.batch,
        seq=args.seq,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        mesh_kind=args.mesh,
        seed=args.seed,
    )
    print(f"[train] done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
