"""Reproduce Fig. 2: FL vs FD vs MixFLD vs Mix2FLD learning curves under
asymmetric and symmetric channels (IID + non-IID).

Run: PYTHONPATH=src python examples/paper_fig2.py [--quick]
Full run writes benchmarks/results/protocols_fig2.json.
"""
import argparse
import sys

sys.path.insert(0, ".")  # allow `benchmarks` import when run from repo root

from benchmarks.bench_protocols import run
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    res = run(quick=args.quick)
    print("\n=== final accuracies ===")
    for k, v in sorted(res.items()):
        print(f"{k:28s} acc={v['acc'][-1]:.3f} "
              f"rounds_converged={v['converged_round']} "
              f"uplink_ok={v['uplink_ok']}")


if __name__ == "__main__":
    main()
