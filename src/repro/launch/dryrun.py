import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
partitions, compiles, and fits — without hardware.

MUST be the first import in the process (XLA locks device count on first
jax init; hence the two lines above precede every other import, including
repro's). Do NOT set this flag anywhere global — smoke tests and benches
see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json

Per cell: jit(step).lower(**input_specs).compile() on the production mesh,
then record memory_analysis() (fits in 16 GB HBM?), cost_analysis() (raw),
the while-aware HLO analysis (corrected flops/bytes/collective bytes), and
the derived roofline terms. Results append to a JSON file (resumable).
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402

from repro.configs import GRID_ARCHS, SHAPES_BY_NAME, TrainConfig, get_config  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import TPU_V5E, make_production_mesh  # noqa: E402
from repro.launch.roofline import derive  # noqa: E402
from repro.launch.steps import build_outer_sync, build_step  # noqa: E402


def _memory_dict(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mode: str = "sync",
    save_hlo: Optional[str] = None,
    overrides: Optional[Dict] = None,
    microbatches: Optional[int] = None,
) -> Dict:
    """Lower + compile one cell; returns the result record."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    if shape.kind == "train":
        # remat=block ("dots without batch dims saveable") saves every
        # activation x weight matmul on these workloads (x@W dots have no
        # dot-level batch dims) — 3x over HBM. Full per-block remat +
        # gradient accumulation is the fitting baseline; selective
        # checkpoint_name policies are a §Perf lever.
        cfg = cfg.replace(remat="full")
    if overrides:
        cfg = cfg.replace(**overrides)
    record: Dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode,
        "status": "skipped",
    }
    if shape_name in cfg.skip_shapes:
        record["skip_reason"] = cfg.skip_reason
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_ways = sizes.get("data", 1) * (sizes.get("pod", 1) if mode == "sync" else 1)
    tokens_per_chip = shape.global_batch * shape.seq_len // max(batch_ways, 1)
    micro = 1
    if shape.kind == "train":
        # activation-memory heuristic: token budget per chip per microbatch,
        # tighter for wide (>10B) and MoE models (dispatch buffers), tightest
        # for the 236B tier
        n = cfg.param_count()
        target = 32768
        if n > 1e10 or cfg.hybrid is not None:
            target = 16384  # wide models / hybrid double-stack residuals
        if n > 1e11 or (cfg.moe is not None and cfg.moe.num_experts):
            target = 8192  # MoE dispatch buffers scale with tokens/chip
        while tokens_per_chip // micro > target and shape.global_batch % (micro * 2 * batch_ways) == 0:
            micro *= 2
    if microbatches is not None:
        micro = microbatches
    tcfg = TrainConfig(
        opt_state_dtype="bfloat16" if cfg.param_count() > 3e10 else "float32",
        optimizer="adafactor" if cfg.param_count() > 1e11 else "adamw",
        microbatches=micro,
    )
    t0 = time.time()
    try:
        built = build_step(cfg, tcfg, shape, mesh, mode=mode)
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                built.fn,
                in_shardings=built.in_shardings,
                out_shardings=built.out_shardings,
                donate_argnums=built.donate_argnums,
            )
            lowered = jitted.lower(*built.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        hlo_text = compiled.as_text()
        cost = analyze_hlo(hlo_text)
        raw = compiled.cost_analysis() or {}
        mem = _memory_dict(compiled)
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo_text)

        terms = derive(
            cfg,
            shape,
            mesh_name=record["mesh"],
            chips=chips,
            flops_per_chip=cost.flops,
            bytes_per_chip=cost.bytes,
            collective_bytes=cost.collective_bytes,
        )
        live_bytes = mem.get("temp_size_in_bytes", 0) + mem.get("argument_size_in_bytes", 0)
        record.update(
            status="ok",
            step_name=built.name,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=mem,
            fits_hbm=bool(live_bytes <= TPU_V5E["hbm_bytes"]),
            cost_analysis_raw={
                k: float(v)
                for k, v in raw.items()
                if k in ("flops", "bytes accessed", "transcendentals")
            },
            hlo={
                "flops_per_chip": cost.flops,
                "bytes_per_chip": cost.bytes,
                "collective_bytes": cost.collective_bytes,
                "unknown_trip_counts": cost.unknown_trip_counts,
                "hlo_chars": len(hlo_text),
            },
            roofline=terms.as_dict(),
        )
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
    return record


def run_outer_sync(arch: str, *, compression: str = "none") -> Dict:
    """Lower the cross-pod FedAvg sync (multi-pod only, the paper's burst)."""
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=True)
    tcfg = TrainConfig(compression=compression)
    record = {"arch": arch, "step": f"outer_sync:{compression}", "mesh": "2x16x16"}
    t0 = time.time()
    try:
        built = build_outer_sync(cfg, tcfg, mesh, compression=compression)
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                built.fn,
                in_shardings=built.in_shardings,
                out_shardings=built.out_shardings,
                donate_argnums=built.donate_argnums,
            )
            compiled = jitted.lower(*built.abstract_args).compile()
        cost = analyze_hlo(compiled.as_text())
        record.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            collective_bytes=cost.collective_bytes,
            memory=_memory_dict(compiled),
        )
    except Exception as e:  # noqa: BLE001
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one architecture id")
    ap.add_argument("--shape", default=None, help="one shape name")
    ap.add_argument("--all", action="store_true", help="all 40 grid cells")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="sync", choices=["sync", "local_sgd"])
    ap.add_argument("--outer-sync", action="store_true",
                    help="also lower the cross-pod FedAvg sync per arch")
    ap.add_argument("--compression", default="none", choices=["none", "int8"])
    ap.add_argument("--out", default=None, help="append results to this JSON")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in GRID_ARCHS:
            for shape in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch + --shape, or --all"
        cells = [(args.arch, args.shape)]

    existing = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    done = {
        (r.get("arch"), r.get("shape"), r.get("mesh"), r.get("mode"))
        for r in existing
    }

    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    results = list(existing)
    for arch, shape in cells:
        key = (arch, shape, mesh_name, args.mode)
        if args.resume and key in done:
            print(f"[skip] {arch} x {shape} ({mesh_name}) already done")
            continue
        print(f"[dryrun] {arch} x {shape} mesh={mesh_name} mode={args.mode} ...", flush=True)
        rec = run_cell(
            arch, shape, multi_pod=args.multi_pod, mode=args.mode,
            save_hlo=args.save_hlo,
        )
        _print_record(rec)
        results.append(rec)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    if args.outer_sync:
        for arch in sorted({a for a, _ in cells}):
            rec = run_outer_sync(arch, compression=args.compression)
            print(f"[outer_sync] {arch}: {rec['status']} "
                  f"coll={rec.get('collective_bytes')}")
            results.append(rec)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_err = sum(1 for r in results if r.get("status") == "error")
    n_skip = sum(1 for r in results if r.get("status") == "skipped")
    print(f"\n== dry-run summary: {n_ok} ok / {n_skip} skipped / {n_err} errors ==")
    return 1 if n_err else 0


def _print_record(rec: Dict):
    if rec["status"] == "ok":
        r = rec["roofline"]
        print(
            f"  ok ({rec['compile_s']}s compile): dominant={r['dominant']} "
            f"compute={r['compute_s']*1e3:.1f}ms memory={r['memory_s']*1e3:.1f}ms "
            f"collective={r['collective_s']*1e3:.1f}ms useful={r['useful_ratio']:.2f} "
            f"fits_hbm={rec['fits_hbm']}"
        )
    elif rec["status"] == "skipped":
        print(f"  skipped: {rec.get('skip_reason','')[:80]}")
    else:
        print(f"  ERROR: {rec.get('error')}")


if __name__ == "__main__":
    raise SystemExit(main())
