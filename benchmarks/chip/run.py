#!/usr/bin/env python3
"""Chip benchmark of the Mix2FLD round: one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the
cell asks for; without them it exits non-zero and prints no result.
Set-up (JAX start-up, the population made on the device, compilation or
cache loads, the cell's first rounds) is timed as ``setup_s``; then the
cell's entry point is stepped for ``--seconds`` and ``rounds_per_s`` is
every round completed over the whole window.  ``--trace 1`` profiles
the window instead and prints the cell's per-layer metrics.  Either way
the first rounds are replayed by the plain reference, and each number
compared is printed beside its limit: the last lines on standard error,
and the ``checks`` key, last in the result, the JSON object on the last
line of standard output.

JAX's persistent compilation cache is kept in ``benchmarks/chip/.cache``
inside the checkout, so only a checkout's first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CACHE = HERE / ".cache" / "jax"


def set_cache_dir() -> None:
    """Points the program's compile cache (``JAX_COMPILATION_CACHE_DIR``)
    at the checkout's fixed directory, caching every program however
    fast it compiled, and puts the benchmark and the program on the
    path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    set_cache_dir()
    from chipbench import harness

    spec = harness.cell_spec(args.workload)
    import jax  # noqa: F401  (after the cache variables are set)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    code, result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, spec=spec)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
