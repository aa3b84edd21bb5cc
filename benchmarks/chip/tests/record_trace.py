#!/usr/bin/env python3
"""Records the test data the metric readers are checked against: one
traced run of a cell on the chip, cut to its first steps.

    python3 benchmarks/chip/tests/record_trace.py --workload <cell> \
        --seed <n> --steps <k> --out benchmarks/chip/testdata/<cell>.trace.json.gz

Writes what the readers read (``metrics/``): the reduced trace of the
first ``--steps`` window steps, the rounds they ran, their length, and
the run's shapes and device kind.
"""
import argparse
import gzip
import json
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]


def cut(run: dict, steps: int) -> dict:
    """``run`` as the harness hands it over, cut to its first steps."""
    rec = run["trace"]
    marks = sorted((h for h in rec["host"] if h[0] == "bench_step"),
                   key=lambda h: h[1])[:steps]
    lo, hi = marks[0][1], marks[-1][1] + marks[-1][2]

    def keep(ev):
        return ev[1] < hi and ev[1] + ev[2] > lo

    trace = {"lines": rec["lines"],
             "device": {plane: {ln: [e for e in evs if keep(e)]
                                for ln, evs in lines.items()}
                        for plane, lines in rec["device"].items()},
             "host": [h for h in rec["host"]
                      if keep(h) and h[0] != "bench_window"]
             + [["bench_window", lo, hi - lo, ""]]}
    per_step = run["rounds"] / len([h for h in rec["host"]
                                    if h[0] == "bench_step"])
    out = {k: v for k, v in run.items() if k not in ("trace", "peaks")}
    out.update(trace=trace, rounds=per_step * len(marks),
               window_s=(hi - lo) / 1e9)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]
    import run as bench

    bench.set_cache_dir()
    from chipbench import harness

    got = {}
    code, _ = harness.run_cell(args.workload, args.seed, args.seconds, True,
                               t_start=time.perf_counter(),
                               on_trace=got.update)
    if code or not got:
        return code or 1
    with gzip.open(args.out, "wt") as f:
        json.dump(cut(got, args.steps), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
