"""round_mfu: the model FLOPs of the rounds completed in the traced
window (``flops.round_flops``: local SGD forward and backward, the eq. 5
conversion, the evaluation forward pass) over the window and the chips'
bf16 peak, in per cent."""
UNIT = "%"


def read(run: dict):
    if not run["rounds"] or not run["flops_per_round"]:
        return None
    achieved = run["flops_per_round"] * run["rounds"] / run["window_s"]
    return 100.0 * achieved / (run["chips"] *
                               run["peaks"]["bf16_flops_per_s"])
