"""Cells of the chip benchmark cut to a size the CPU runs in seconds,
for the tests: the committed files, with the sizes below in place."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

from chipbench import harness  # noqa: E402


def spec(name: str) -> dict:
    s = harness.cell_spec(name)
    pool = s["traffic"].get("cohort") is not None
    s["config"].update(num_devices=40 if pool else 4, samples_per_device=32,
                       test_samples=64, local_iters=6, server_iters=4,
                       n_seed=4, n_inverse=8)
    s["config"]["channel"]["num_devices"] = 8 if pool else 4
    if pool:
        s["traffic"].update(cohort=8, sample_ratio=8 / 40, ckpt_every=2)
    if "grid" in s["traffic"]:
        # a step size at which 6 local steps learn, as 200 do at the
        # cell's size: the sweep compares only losses and the seed set,
        # and a fault shows in a loss only once the model learns
        s["traffic"].update(grid={"eta": [0.5], "p_up_dbm": [23.0, 40.0]},
                            rounds_per_run=3)
        s["workload"]["check_rounds"] = 2
    return s


def run(name: str, seconds: float = 0.5, **kw) -> dict:
    """One run of the tiny cell on the CPU, past the harness's look for
    a chip; returns the result line."""
    code, result = harness.run_cell(name, 2 ** 31 + 7, seconds, False,
                                    t_start=0.0, require_chip=False,
                                    spec=spec(name), **kw)
    assert code == 0
    return result
