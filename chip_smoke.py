"""Smoke test of the FL round engine on one TPU chip.

Runs the paper's deployment (the MNIST CNN at full width, 10 clients x 200
examples, FedAvg, 8 rounds x 4 local steps) through the engine's entry
points, ``FederatedServer`` and ``run_fl_grid``, in this one process:

  a. device  - JAX's first device is a TPU, and the Pallas kernels run
               compiled rather than interpreted;
  b. kernels - ``fedavg_reduce``, ``quantize_rows`` and
               ``downcast_bf16_rows`` at the CNN's leaf shapes, R in
               {1, 10, 32}, agree with the ``kernels/ref.py`` oracles
               (bitwise for the two quantizers) and compile to Mosaic
               custom calls; the int8 and bf16 compressors give the same
               bits stacked over the cohort as client by client;
  c. server  - one int8 and one bf16 point on the device transport plane
               train 8/8 rounds past 0.5 accuracy, with the kernels, and
               no oracle, on the compress and aggregate path;
  d. grid    - a 4-point int8 grid through ``run_fl_grid(transport=
               "parity")`` equals the 4 per-point runs bitwise: every
               ``History.summary()`` and every final global param;
  e. fused   - the same grid on the fused device transport plane completes.

Each phase prints one JSON line with its result and wall time. The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed. A failed phase prints its traceback and makes
the script exit 1; a machine without a TPU exits 2 before any work.

  python chip_smoke.py
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import spawn_point_seeds  # noqa: E402
from repro.chaos import ChaosSchedule  # noqa: E402
from repro.compress import get_compressor, init_residual_plane  # noqa: E402
from repro.core import (  # noqa: E402
    EdgeClient,
    FederatedServer,
    GridPoint,
    ServerConfig,
    fedavg,
    mnist_cnn_task,
    run_fl_grid,
)
from repro.core import strategy as strategy_mod  # noqa: E402
from repro.data import make_federated_mnist, synthetic_mnist  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.transport import DEFAULT, LAB, TUNED_EDGE  # noqa: E402
from repro.utils import tree_stack, tree_unstack  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

KERNEL_ROWS = (1, 10, 32)
# phase d's grid: (one-way delay in s, TCP parameters) per point
GRID_SPECS = ((0.05, DEFAULT), (2.0, DEFAULT), (5.0, DEFAULT), (5.0, TUNED_EDGE))


class NoTPU(RuntimeError):
    """JAX found no TPU: the smoke test has nothing to run on."""


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The FL deployment every phase runs (defaults: the paper's)."""

    n_clients: int = 10
    examples_per_client: int = 200
    rounds: int = 8
    local_steps: int = 4
    eval_examples: int = 400
    min_accuracy: float = 0.5  # chance is 0.1


class Fixture:
    """Task, shards and eval set shared by every phase: the jit caches live
    on the task, and the grid coalesces rows by dataset identity."""

    def __init__(self, deploy: Deployment, *, seed: int = 0):
        self.deploy = deploy
        self.task = mnist_cnn_task()
        self.shards = make_federated_mnist(
            deploy.n_clients, deploy.examples_per_client, seed=seed
        )
        self.eval_data = synthetic_mnist(deploy.eval_examples, seed=4242)

    def point(self, *, seed=0, link=LAB, tcp=DEFAULT, compressor=None,
              rng_streams="single", transport_backend="host") -> GridPoint:
        d = self.deploy
        return GridPoint(
            [EdgeClient(i, dataset=s) for i, s in enumerate(self.shards)],
            fedavg(min_fit=0.5),
            tcp,
            ChaosSchedule(link),
            ServerConfig(
                rounds=d.rounds, local_steps=d.local_steps, seed=seed,
                batched=True, stochastic=True, rng_streams=rng_streams,
                transport_backend=transport_backend,
            ),
            compressor=compressor,
        )

    def run_point(self, p: GridPoint) -> FederatedServer:
        server = FederatedServer(
            self.task, p.clients, p.strategy, tcp=p.tcp, chaos=p.chaos,
            config=p.config, compressor=p.compressor, eval_data=self.eval_data,
        )
        server.run()
        return server

    def grid_points(self, compressor, transport_backend="host"):
        seeds = spawn_point_seeds(len(GRID_SPECS))
        return [
            self.point(
                seed=s, link=LAB.replace(delay=owd, name=f"owd{owd}"), tcp=tcp,
                compressor=compressor, rng_streams="split",
                transport_backend=transport_backend,
            )
            for (owd, tcp), s in zip(GRID_SPECS, seeds)
        ]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device() -> dict:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise NoTPU(f"no TPU found: JAX's first device is {dev.platform!r}")
    if ops.default_interpret():
        raise RuntimeError("Pallas kernels would run in interpret mode on a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def phase_kernels(fx: Fixture, rows=KERNEL_ROWS, *, interpret=False) -> dict:
    """Each kernel over every CNN leaf, as one program per row count R.

    Quantizers must equal their oracle bitwise. The reduce must agree to
    1e-6 of the leaf's largest magnitude: it sums in f32 in another order
    than the oracle's einsum, so an element that cancels to near zero has
    no elementwise relative bound. Compiled (not interpreted), every leaf
    must be one Mosaic custom call.
    """
    shapes = [l.shape for l in jax.tree.leaves(
        jax.eval_shape(fx.task.init_fn, jax.random.PRNGKey(0)))]
    quant = jax.jit(lambda xs, ss: [ops.quantize_rows(x, s, interpret=interpret)
                                    for x, s in zip(xs, ss)])
    bf16 = jax.jit(lambda xs: [ops.downcast_bf16_rows(x, interpret=interpret)
                               for x in xs])
    reduce = jax.jit(lambda tree, w: ops.fedavg_reduce(tree, w, interpret=interpret))
    quant_ref = jax.jit(lambda xs, ss: [ref.quantize_rows_ref(x, s)
                                        for x, s in zip(xs, ss)])
    bf16_ref = jax.jit(lambda xs: [ref.downcast_bf16_rows_ref(x) for x in xs])
    reduce_ref = jax.jit(lambda xs, w: [ref.fedavg_reduce_ref(x, w / jnp.sum(w))
                                        for x in xs])

    key = jax.random.PRNGKey(0)
    out = {"leaves": len(shapes), "rows": list(rows), "reduce_max_rel_err": 0.0}
    for r in rows:
        key, kw, *kx = jax.random.split(key, 2 + len(shapes))
        stacked = [jax.random.normal(k, (r,) + s, jnp.float32) for k, s in zip(kx, shapes)]
        flat = [x.reshape(r, -1) for x in stacked]
        scales = [jnp.max(jnp.abs(x), axis=-1) * (1.0 / 127.0) for x in flat]
        w = jax.random.uniform(kw, (r,)) + 0.05

        for name, fn, args, oracle in [
            ("quantize_rows", quant, (flat, scales), quant_ref(flat, scales)),
            ("downcast_bf16_rows", bf16, (flat,), bf16_ref(flat)),
            ("fedavg_reduce", reduce, (stacked, w), reduce_ref(flat, w)),
        ]:
            compiled = fn.lower(*args).compile()
            got = jax.tree.leaves(compiled(*args))
            if not interpret and _custom_calls(compiled) < len(shapes):
                raise AssertionError(
                    f"{name} R={r}: {_custom_calls(compiled)} Mosaic custom calls "
                    f"for {len(shapes)} leaves"
                )
            for g, o in zip(got, oracle):
                g, o = np.asarray(g), np.asarray(o).reshape(g.shape)
                if name != "fedavg_reduce":
                    if not np.array_equal(g, o):
                        bad = int(np.sum(g != o))
                        raise AssertionError(
                            f"{name} R={r} leaf {g.shape}: {bad} elements differ "
                            "from the oracle"
                        )
                    continue
                err = float(np.max(np.abs(g - o)) / max(np.max(np.abs(o)), 1e-30))
                out["reduce_max_rel_err"] = max(out["reduce_max_rel_err"], err)
                if err > 1e-6:
                    raise AssertionError(
                        f"fedavg_reduce R={r} leaf {g.shape}: relative error {err}"
                    )
    return out


def phase_compress(fx: Fixture, rounds: int = 2) -> dict:
    """The int8 and bf16 compressors at the CNN's leaf shapes: the stacked
    plane (one row kernel call over the cohort) equals client-by-client
    compression (R = 1), bitwise, in every decompressed update and every
    error-feedback residual, round after round."""
    n = fx.deploy.n_clients
    leaves, treedef = jax.tree.flatten(
        jax.eval_shape(fx.task.init_fn, jax.random.PRNGKey(0)))
    key = jax.random.PRNGKey(1)

    def delta(i, rnd):
        return treedef.unflatten([
            0.01 * jax.random.normal(
                jax.random.fold_in(key, (rnd * n + i) * len(leaves) + j), l.shape)
            for j, l in enumerate(leaves)
        ])

    slots = list(range(n))[::-1]  # clients land on arbitrary plane rows
    for name in ("int8", "bf16"):
        comp = get_compressor(name)
        seq_res = [None] * n
        plane_res = init_residual_plane(treedef.unflatten(leaves), n)
        for rnd in range(rounds):
            deltas = [delta(i, rnd) for i in range(n)]
            seq_out = []
            for d, s in zip(deltas, slots):
                payload, seq_res[s] = comp.compress(d, seq_res[s])
                seq_out.append(comp.decompress(payload))
            plane_out, plane_res = comp.compress_plane(
                tree_stack(deltas), plane_res, jnp.asarray(slots))
            pairs = [(f"update {j}", seq_out[j], row)
                     for j, row in enumerate(tree_unstack(plane_out))]
            pairs += [(f"residual {s}", seq_res[s],
                       jax.tree.map(lambda l, s=s: l[s], plane_res)) for s in slots]
            for what, a, b in pairs:
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
                    if not np.array_equal(np.asarray(x).reshape(y.shape), np.asarray(y)):
                        raise AssertionError(
                            f"{name} round {rnd}: plane != sequential in {what}")
    return {"compressors": ["int8", "bf16"], "rows": n, "rounds": rounds}


@contextlib.contextmanager
def hot_path_calls():
    """Count the kernel wrappers and the XLA oracles that the compress and
    aggregate path calls (at trace time for the jitted compressor, per
    round for the aggregate), and every ``interpret=`` they were given."""
    calls = {"kernels": 0, "oracles": 0, "interpret": []}
    patches = [(ops, n, "kernels") for n in
               ("quantize_rows", "downcast_bf16_rows", "fedavg_reduce")]
    patches += [(ref, n, "oracles") for n in
                ("quantize_rows_ref", "downcast_bf16_rows_ref")]
    patches += [(strategy_mod, "_stacked_mean_xla", "oracles")]

    def spy(fn, kind):
        def wrapped(*a, **kw):
            calls[kind] += 1
            if "interpret" in kw:
                calls["interpret"].append(bool(kw["interpret"]))
            return fn(*a, **kw)
        return wrapped

    saved = [(mod, n, getattr(mod, n)) for mod, n, _ in patches]
    for mod, n, kind in patches:
        setattr(mod, n, spy(getattr(mod, n), kind))
    try:
        yield calls
    finally:
        for mod, n, fn in saved:
            setattr(mod, n, fn)


def phase_server(fx: Fixture, compressor: str, *, kernels=True) -> dict:
    """One run_fl_experiment-shaped point, on the device transport plane."""
    d = fx.deploy
    p = fx.point(compressor=get_compressor(compressor), transport_backend="device")
    with hot_path_calls() as calls:
        s = fx.run_point(p).history.summary()
    out = {"compressor": compressor, "completed_rounds": s["completed_rounds"],
           "final_accuracy": s["final_accuracy"], "kernel_calls": calls["kernels"],
           "oracle_calls": calls["oracles"]}
    if s["completed_rounds"] != d.rounds:
        raise AssertionError(f"{compressor}: {s['completed_rounds']}/{d.rounds} rounds")
    if not s["final_accuracy"] > d.min_accuracy:
        raise AssertionError(f"{compressor}: final accuracy {s['final_accuracy']}")
    if kernels and (calls["kernels"] == 0 or calls["oracles"] or any(calls["interpret"])):
        raise AssertionError(f"{compressor}: hot path did not run on the kernels: {calls}")
    return out


def _same_summary(a: dict, b: dict) -> bool:
    return all(a[k] == b[k] or (a[k] != a[k] and b[k] != b[k]) for k in a)


def phase_grid(fx: Fixture) -> dict:
    """Grid (parity transport) == per-point runs, bitwise."""
    comp = get_compressor("int8")
    grid = run_fl_grid(fx.task, fx.grid_points(comp), eval_data=fx.eval_data,
                       transport="parity")
    per_point = [fx.run_point(p) for p in fx.grid_points(comp)]
    out = {"points": []}
    for i, (hist, gsrv, psrv) in enumerate(zip(grid.histories, grid.servers, per_point)):
        gs, ps = hist.summary(), psrv.history.summary()
        if not _same_summary(gs, ps):
            raise AssertionError(f"point {i}: grid {gs} != per-point {ps}")
        gflat = jax.tree_util.tree_flatten_with_path(gsrv.global_params)[0]
        for (kp, g), q in zip(gflat, jax.tree.leaves(psrv.global_params), strict=True):
            if not np.array_equal(np.asarray(g), np.asarray(q)):
                raise AssertionError(
                    f"point {i}: final params differ at {jax.tree_util.keystr(kp)}"
                )
        out["points"].append({k: gs[k] for k in ("completed_rounds", "final_accuracy")})
    out["fit_rows_total"] = grid.stats.fit_rows_total
    out["fit_rows_unique"] = grid.stats.fit_rows_unique
    return out


def phase_fused(fx: Fixture) -> dict:
    """The same grid on the fused device transport plane completes."""
    res = run_fl_grid(
        fx.task, fx.grid_points(get_compressor("int8"), transport_backend="device"),
        eval_data=fx.eval_data, transport="fused",
    )
    if res.stats.transport_device_dispatches == 0:
        raise AssertionError("fused grid made no device transport dispatch")
    rounds = [h.summary()["completed_rounds"] for h in res.histories]
    if rounds[0] != fx.deploy.rounds:
        raise AssertionError(f"0.05 s point completed {rounds[0]}/{fx.deploy.rounds}")
    return {"completed_rounds": rounds,
            "device_dispatches": res.stats.transport_device_dispatches}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.split("\n\n")[0]).parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = phase_device()
    except NoTPU as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    _emit({"phase": "device", "ok": True, **device,
           "seconds": time.perf_counter() - t0})

    fx = Fixture(Deployment())
    phases = [
        ("kernels", lambda: phase_kernels(fx)),
        ("compress_parity", lambda: phase_compress(fx)),
        ("server_int8", lambda: phase_server(fx, "int8")),
        ("server_bf16", lambda: phase_server(fx, "bf16")),
        ("grid_parity", lambda: phase_grid(fx)),
        ("grid_fused", lambda: phase_fused(fx)),
    ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            result, ok = fn(), True
        except Exception as e:  # reported, and fails the run below
            traceback.print_exc()
            result, ok = {"error": f"{type(e).__name__}: {e}"}, False
            failed.append(name)
        _emit({"phase": name, "ok": ok, **result,
               "seconds": time.perf_counter() - t0})
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
