"""distill_kernel_roofline: the fused distillation-loss kernels
(``kernels/distill_loss.py``, forward and backward) against their
roofline.  For every kernel event in the traced window the least time
the chip could take is max(ops / peak FLOP/s, bytes / peak HBM bytes/s)
at the call's shape (``flops.distill_kernel_cost``); the share is the
sum of those over the summed device time of the events, in per cent.
At these shapes the bytes bound it (about one operation per byte)."""
from chipbench import flops, xtrace

UNIT = "%"


def kernel_of(name: str, label: str):
    """``fwd``/``bwd`` for the kernels' events, else None.  On the TPU
    an event is named after the custom call of the jitted wrapper
    (``%jvp_jit__phi_psi_fwd_call__.10``,
    ``%transpose_jvp_jit__phi_psi_bwd_call___.8``)."""
    text = f"{name} {label}"
    if "phi_psi_bwd" in text:
        return "bwd"
    if "phi_psi" in text:
        return "fwd"
    return None


def read(run: dict):
    kern = run.get("kernel")
    if not kern:
        return None
    events = xtrace.events_in_window(
        run["trace"], "XLA Ops", lambda n, lb: kernel_of(n, lb) is not None)
    if not events:
        return None
    cost = flops.distill_kernel_cost(kern["rows"], kern["classes"])
    pk = run["peaks"]
    least = spent = 0.0
    for name, s, e, label, _ in events:
        c = cost[kernel_of(name, label)]
        least += max(c["ops"] / pk["bf16_flops_per_s"],
                     c["bytes"] / pk["hbm_bytes_per_s"])
        spent += (e - s) / 1e9
    return 100.0 * least / spent
