"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Full (paper-scale) variants
run via each module's __main__; here the quick variants keep the whole
suite CPU-tractable.  Protocol-grid modules (protocols, seed_sweep) run
on the compiled sweep engine (repro.sweep) — whole grids per program —
and seed_sweep also records the engine's sweep-vs-loop speedup
(benchmarks/results/sweep_engine.json).

Select a subset by name: ``python -m benchmarks.run seed_sweep kernels``.
``--quick`` propagates to every module whose ``main`` accepts a
``quick`` keyword (payload frontier, privacy tables) — the regime CI
runs and the committed baselines are generated under.
"""
from __future__ import annotations

import inspect
import sys
import traceback


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import (bench_kernels, bench_models, bench_payload,
                   bench_pipeline, bench_privacy, bench_protocols,
                   bench_roofline, bench_sampling, bench_scalability,
                   bench_seed_sweep, bench_service)

    modules = [
        ("payload", bench_payload),      # Sec. II-C / IV payload ratios
        ("privacy", bench_privacy),      # Tables II & III
        ("kernels", bench_kernels),      # Pallas kernels vs oracles
        ("roofline", bench_roofline),    # dry-run roofline terms
        ("protocols", bench_protocols),  # Fig. 2 (quick, sweep engine)
        ("seed_sweep", bench_seed_sweep),  # (N_S, N_I) grid + engine speedup
        ("scalability", bench_scalability),  # Fig. 3 (quick)
        ("sampling", bench_sampling),    # rounds/s vs sample_ratio
        ("service", bench_service),      # ckpt overhead + resume fidelity
        ("pipeline", bench_pipeline),    # async rounds + 2-D mesh sweep
        ("models", bench_models),        # heterogeneous model x task grid
    ]
    args = list(sys.argv[1:] if argv is None else argv)
    quick = "--quick" in args
    wanted = {a for a in args if a != "--quick"}
    if wanted:
        unknown = wanted - {n for n, _ in modules}
        if unknown:
            raise SystemExit(f"unknown benchmark module(s): "
                             f"{sorted(unknown)}; "
                             f"available: {[n for n, _ in modules]}")
        modules = [(n, m) for n, m in modules if n in wanted]
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        try:
            kwargs = {}
            if quick and "quick" in inspect.signature(mod.main).parameters:
                kwargs["quick"] = True
            for row in mod.main(**kwargs):
                print(row)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
