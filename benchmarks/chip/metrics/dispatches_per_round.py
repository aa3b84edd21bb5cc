"""dispatches_per_round: device program launches (events of the
``XLA Modules`` line) in the traced window per round, averaged over
the chips."""
from chipbench import xtrace

UNIT = "launches/round"


def read(run: dict):
    launches = xtrace.events_in_window(run["trace"], "XLA Modules",
                                       lambda name, label: True)
    planes = {ev[4] for ev in launches}
    if not launches or not run["rounds"]:
        return None
    return len(launches) / len(planes) / run["rounds"]
