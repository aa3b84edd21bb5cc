"""local_sgd_ms: device time of the jitted local-SGD program (the
``XLA Modules`` events named after it) per round, per chip."""
from chipbench import xtrace

UNIT = "ms/round"


def read(run: dict):
    return xtrace.module_ms_per_round(run, run["programs"].get("local_train"))
