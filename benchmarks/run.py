"""Benchmark entry point: one benchmark per paper table/figure.

``python -m benchmarks.run``          — full sweeps
``python -m benchmarks.run --fast``   — thinned sweeps (CI)

Prints each benchmark's CSV block plus a ``name,seconds,status`` summary.
The 40-cell dry-run + roofline table is separate (compile-heavy):
``python -m repro.launch.dryrun --all`` (see EXPERIMENTS.md).
"""

import argparse
import contextlib
import signal
import sys
import time
import traceback


@contextlib.contextmanager
def _wall_clock_budget(seconds):
    """Per-bench wall-clock budget via SIGALRM: a bench that blows its
    budget raises TimeoutError and is reported as a loud FAIL instead of
    silently eating the whole CI allotment. No-op when ``seconds`` is
    None or SIGALRM is unavailable (non-main thread / non-POSIX)."""
    if seconds is None or not hasattr(signal, "SIGALRM"):
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(f"benchmark exceeded --max-seconds={seconds}")

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(int(seconds))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="thinned sweeps")
    ap.add_argument(
        "--only", default=None,
        help="run selected benchmarks (comma-separated names)",
    )
    ap.add_argument(
        "--max-seconds", type=int, default=None,
        help="per-benchmark wall-clock budget; exceeding it fails that bench",
    )
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (
        adaptive_daemon,
        async_bench,
        compress_bench,
        env_profiles,
        fig3_latency,
        fig4_loss,
        fig5_client_failure,
        fig678_tcp_params,
        kernel_bench,
        population_bench,
        reliability_bench,
        resilience_bench,
        round_engine_bench,
        sweep_bench,
        table3_boundaries,
        transport_plane_bench,
        tuned_vs_default,
    )

    benches = [
        ("env_profiles", env_profiles.main),          # Tables I & II
        ("fig3_latency", fig3_latency.main),          # Fig 3
        ("fig4_loss", fig4_loss.main),                # Fig 4
        ("fig5_client_failure", fig5_client_failure.main),  # Fig 5
        ("fig678_tcp_params", fig678_tcp_params.main),  # Figs 6-8 + Table IV
        ("table3_boundaries", table3_boundaries.main),  # Table III
        ("tuned_vs_default", tuned_vs_default.main),  # SecV validation
        ("adaptive_daemon", adaptive_daemon.main),    # beyond-paper (SecVI)
        ("kernel_bench", kernel_bench.main),
        ("round_engine_bench", round_engine_bench.main),
        ("sweep_bench", sweep_bench.main),
        ("compress_bench", compress_bench.main),
        ("transport_plane_bench", transport_plane_bench.main),
        ("resilience_bench", resilience_bench.main),
        ("reliability_bench", reliability_bench.main),  # SecVI reliability frontier
        ("async_bench", async_bench.main),
        ("population_bench", population_bench.main),  # million-client plane
    ]

    if only is not None:
        valid = [name for name, _ in benches]
        unknown = only - set(valid)
        if unknown:
            # a typo here would silently skip a bench (and its parity
            # gate) while CI stays green
            print(
                f"unknown benchmark(s): {sorted(unknown)}; "
                f"valid names: {', '.join(valid)}",
                file=sys.stderr,
            )
            sys.exit(2)

    summary = []
    failed = 0
    for name, fn in benches:
        if only is not None and name not in only:
            continue
        print(f"\n##### {name} #####")
        t0 = time.time()
        try:
            with _wall_clock_budget(args.max_seconds):
                fn(fast=args.fast)
            status = "ok"
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            status = f"FAIL:{type(e).__name__}"
            failed += 1
        except SystemExit as e:
            # parity gates exit via SystemExit; keep per-bench isolation
            # so the remaining benches and the summary still run
            status = f"FAIL:exit{e.code}"
            failed += 1
        summary.append((name, round(time.time() - t0, 1), status))

    print("\n##### summary #####")
    print("name,seconds,status")
    for name, dt, status in summary:
        print(f"{name},{dt},{status}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
