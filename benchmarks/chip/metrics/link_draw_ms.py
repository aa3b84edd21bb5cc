"""link_draw_ms: host milliseconds per round inside the program's
``link_draw`` spans in the traced window: dispatch of the channel and
straggler draws, and the readbacks that collect them (the wait for
device work queued before them included)."""
from chipbench import xtrace

UNIT = "ms/round"
SPANS = ("link_draw",)


def read(run: dict):
    if not run["rounds"]:
        return None
    lo, hi = xtrace.window(run["trace"])
    ns = [min(s + d, hi) - max(s, lo)
          for name, s, d, _ in run["trace"]["host"]
          if name in SPANS and min(s + d, hi) > max(s, lo)]
    return sum(ns) / 1e6 / run["rounds"] if ns else None
