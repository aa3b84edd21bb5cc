"""eval_ms: host milliseconds per round inside the program's
``evaluate`` and ``converge`` spans in the traced window: the accuracy
program, the convergence norm and their readbacks, which also wait for
the round's device work queued before them."""
from chipbench import xtrace

UNIT = "ms/round"
SPANS = ("evaluate", "converge")


def read(run: dict):
    if not run["rounds"]:
        return None
    lo, hi = xtrace.window(run["trace"])
    ns = [min(s + d, hi) - max(s, lo)
          for name, s, d, _ in run["trace"]["host"]
          if name in SPANS and min(s + d, hi) > max(s, lo)]
    return sum(ns) / 1e6 / run["rounds"] if ns else None
