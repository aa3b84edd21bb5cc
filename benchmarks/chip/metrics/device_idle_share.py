"""device_idle_share: 1 - (union of the intervals in which an operation
ran on the device) / (traced window), averaged over the chips."""
from chipbench import xtrace

UNIT = "fraction"


def read(run: dict):
    rec = run["trace"]
    if not xtrace.device_events(rec, "XLA Ops"):
        return None
    return 1.0 - xtrace.busy_s(rec) / xtrace.window_s(rec)
