"""Operations and bytes the algorithm needs, from shapes alone.

A FLOP is one multiply or one add, so a multiply-accumulate counts 2.
Only the convolutions and the dense layer are counted (bias adds, ReLU,
pooling and the optimizer update are a few per cent of the work at
these shapes and are left out); nothing recomputed is counted, and
conv1 needs no gradient of its input.
"""
from __future__ import annotations


def cnn_layer_flops(config: dict) -> dict:
    """Forward FLOPs of one sample, per layer, for the 3-layer CNN
    (2 SAME convolutions, each followed by 2x2 max pooling, then FC)."""
    h, w, cin = (int(s) for s in config["input_shape"])
    c1, c2 = (int(c) for c in config["conv_channels"])
    k, pool, C = int(config["kernel"]), int(config["pool"]), \
        int(config["num_classes"])
    h2, w2 = h // pool, w // pool
    fc_in = (h2 // pool) * (w2 // pool) * c2
    return {"conv1": 2 * h * w * c1 * k * k * cin,
            "conv2": 2 * h2 * w2 * c2 * k * k * c1,
            "fc": 2 * fc_in * C}


def cnn_flops(config: dict) -> tuple[int, int]:
    """(forward, forward + backward) FLOPs of one sample: the backward
    pass takes every weight gradient and every input gradient but
    conv1's."""
    f = cnn_layer_flops(config)
    fwd = sum(f.values())
    return fwd, 2 * fwd + f["conv2"] + f["fc"]


def round_flops(config: dict, *, trained_devices: int, convert: bool,
                points: int = 1) -> float:
    """Model FLOPs of one federated round at each of ``points`` grid
    points: local SGD on every trained device, the eq. 5 conversion
    (FLD family), and the evaluation forward pass over the test set."""
    fwd, fwd_bwd = cnn_flops(config)
    local = trained_devices * int(config["local_iters"]) * \
        int(config["local_batch"]) * fwd_bwd
    conv = (int(config["server_iters"]) * int(config["server_batch"]) *
            fwd_bwd if convert else 0)
    evaluate = int(config["test_samples"]) * fwd
    return float(points * (local + conv + evaluate))


def distill_kernel_cost(rows: int, classes: int) -> dict:
    """Operations and HBM bytes of one call of each fused distillation
    kernel over ``rows`` samples of ``classes`` logits (float32 logits
    and KD target rows, int32 labels, float32 per-sample outputs).

    Forward, per row: max, shift, exp and sum over the row, the log, the
    label pick (compare, select, sum), the KD row sum and dot product:
    10 C + 5.  Backward, per row: the softmax (max, shift, exp, sum,
    divide), the label pick, the KD row sum, both cotangent terms and
    d psi / d g: 15 C + 3.
    """
    n, c = rows, classes
    return {
        "fwd": {"ops": n * (10 * c + 5), "bytes": 4 * n * (2 * c + 3)},
        "bwd": {"ops": n * (15 * c + 3), "bytes": 4 * n * (4 * c + 3)},
    }
