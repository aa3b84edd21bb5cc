"""convert_ms: device time of the jitted eq. 5 conversion program
(``output_to_model``) per round, per chip."""
from chipbench import xtrace

UNIT = "ms/round"


def read(run: dict):
    return xtrace.module_ms_per_round(run, run["programs"].get("convert"))
