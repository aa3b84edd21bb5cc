"""cohort_io_ms: host milliseconds per round inside the program's
``cohort_io`` spans in the traced window: building the cohort's
indices, gathering its state and data off the device pool, and
scattering its state back."""
from chipbench import xtrace

UNIT = "ms/round"
SPANS = ("cohort_io",)


def read(run: dict):
    if not run["rounds"]:
        return None
    lo, hi = xtrace.window(run["trace"])
    ns = [min(s + d, hi) - max(s, lo)
          for name, s, d, _ in run["trace"]["host"]
          if name in SPANS and min(s + d, hi) > max(s, lo)]
    return sum(ns) / 1e6 / run["rounds"] if ns else None
