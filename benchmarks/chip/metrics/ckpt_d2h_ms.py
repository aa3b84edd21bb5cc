"""ckpt_d2h_ms: host milliseconds per save inside the program's
``checkpoint.d2h`` spans (the device-to-host copy of the checkpoint's
arrays), over the ``checkpoint`` spans that start in the traced
window."""
from chipbench import xtrace

UNIT = "ms/save"


def read(run: dict):
    lo, hi = xtrace.window(run["trace"])
    host = run["trace"]["host"]
    saves = sum(1 for name, s, _, _ in host
                if name == "checkpoint" and lo <= s < hi)
    ns = [min(s + d, hi) - max(s, lo) for name, s, d, _ in host
          if name == "checkpoint.d2h" and min(s + d, hi) > max(s, lo)]
    return sum(ns) / 1e6 / saves if saves and ns else None
