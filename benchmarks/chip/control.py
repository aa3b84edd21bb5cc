#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, the control and a
planted fault, each against the float32 reference, over many seeds.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,3,... [--control-seeds 3] [--out FILE]

For every seed, set-up drives the cell's program through its first
rounds exactly as a benchmark run does, and the reference replays them.
On the first ``--control-seeds`` seeds it also replays them with the
control (the reference in bfloat16, the precision below the float32
the configuration states) and with half of every local batch left out,
each compared with the float32 reference as the program is.  The
benchmark's own runs never run this.  Prints one JSON line per reading;
the lower reading of a number is the largest the program gives, the
upper the smallest the control or a fault gives.
"""
import argparse
import json
import time
from pathlib import Path


def readings(name: str, seeds: list, control_seeds: int, *,
             require_chip: bool = True, spec=None) -> list:
    import gc

    import jax.numpy as jnp

    from chipbench import checks, harness

    spec = spec or harness.cell_spec(name)
    if require_chip:
        why = harness.require_chips(int(spec["entry"]["chips"]))
        if why:
            raise SystemExit(f"control: {why}")
    driver = harness.load_module("drivers", spec["workload"]["driver"],
                                 spec["chip"])
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        cell = driver.setup(harness.Context(spec, seed))
        cell.release()
        gc.collect()
        ref = cell.reference_records()
        rows.append({"seed": seed, "run": "program",
                     **checks.compare(cell.program_records(), ref)})
        if i < control_seeds:
            for run, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                            ("fault_half_batch", {"fault": "half_batch"})):
                rows.append({"seed": seed, "run": run, **checks.compare(
                    cell.reference_records(**kw), ref)})
        rows[-1]["seconds"] = time.perf_counter() - t0
        cell = ref = None   # free this seed's population before the next
        gc.collect()
        for r in rows[-(1 if i >= control_seeds else 3):]:
            print(json.dumps(r), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import run as bench

    bench.set_cache_dir()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(args.workload, seeds, args.control_seeds)
    if args.out:
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
