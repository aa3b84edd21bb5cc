"""Every per-layer metric reader against traces recorded on a TPU v5e
chip (``testdata/``: a few rounds of the loop and service cells, cut
from a traced run by ``tests/record_trace.py``), each number worked out
here a second way."""
import gzip
import json

import numpy as np
import pytest

import chipbench_tiny as tiny

from chipbench import flops, harness, peaks, xtrace  # noqa: E402

DATA = tiny.CHIP / "testdata"


def load(cell: str) -> dict:
    with gzip.open(DATA / f"{cell}.trace.json.gz", "rt") as f:
        run = json.load(f)
    run["peaks"] = peaks.peaks(run.pop("device_kind"))
    return run


def read(metric: str, run: dict):
    return harness.load_module("metrics", metric).read(run)


@pytest.fixture(params=["paper-mix2fld-loop", "population-fd-service"])
def run(request):
    return load(request.param)


def _window(run):
    (lo, hi), = [(s, s + d) for n, s, d, _ in run["trace"]["host"]
                 if n == "bench_window"]
    return lo, hi


def _device_lines(run, line):
    return [lines[line] for lines in run["trace"]["device"].values()
            if line in lines]


def test_idle_share_against_a_timeline(run):
    lo, hi = _window(run)
    busy = np.zeros(-(-(hi - lo) // 1000), bool)     # 1 us cells
    (ops,) = _device_lines(run, "XLA Ops")
    for _, s, d, _ in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            busy[(a - lo) // 1000:-(-(b - lo) // 1000)] = True
    got = read("device_idle_share", run)
    assert 0.0 < got < 1.0
    assert got == pytest.approx(1.0 - busy.mean(), abs=2e-3)


def test_dispatches_per_round(run):
    lo, hi = _window(run)
    (mods,) = _device_lines(run, "XLA Modules")
    n = sum(1 for _, s, d, _ in mods if min(s + d, hi) > max(s, lo))
    assert read("dispatches_per_round", run) == n / run["rounds"]
    assert n > run["rounds"]


def test_local_sgd_ms(run):
    lo, hi = _window(run)
    (mods,) = _device_lines(run, "XLA Modules")
    ms = sum(min(s + d, hi) - max(s, lo) for name, s, d, _ in mods
             if "local_train" in name and min(s + d, hi) > max(s, lo)) / 1e6
    got = read("local_sgd_ms", run)
    assert got == pytest.approx(ms / run["rounds"])
    assert got > 0.0


def test_convert_ms_only_where_the_program_runs():
    loop = load("paper-mix2fld-loop")
    assert read("convert_ms", loop) > 0.0
    # the fd service never converts: the reader finds nothing and
    # returns nothing, so the harness leaves the metric out
    assert read("convert_ms", load("population-fd-service")) is None


def test_distill_kernel_roofline(run):
    lo, hi = _window(run)
    (ops,) = _device_lines(run, "XLA Ops")
    cost = flops.distill_kernel_cost(run["kernel"]["rows"],
                                     run["kernel"]["classes"])
    least = spent = 0.0
    for name, s, d, label in ops:
        text = name + " " + label
        kind = ("bwd" if "_phi_psi_bwd_call" in text else
                "fwd" if "_phi_psi_fwd_call" in text else None)
        if kind and min(s + d, hi) > max(s, lo):
            least += cost[kind]["bytes"] / run["peaks"]["hbm_bytes_per_s"]
            spent += (min(s + d, hi) - max(s, lo)) / 1e9
    assert spent > 0.0
    got = read("distill_kernel_roofline", run)
    assert got == pytest.approx(100.0 * least / spent)
    assert 0.0 < got <= 100.0


def test_round_mfu(run):
    want = 100.0 * run["flops_per_round"] * run["rounds"] / \
        run["window_s"] / 197e12
    assert read("round_mfu", run) == pytest.approx(want)


def test_ckpt_stall_ms():
    svc = load("population-fd-service")
    assert read("ckpt_stall_ms", svc) == pytest.approx(
        np.mean(svc["saves_ms"]))
    assert read("ckpt_stall_ms", load("paper-mix2fld-loop")) is None


def test_breakdown_lists_ops_and_gaps(run):
    bd = xtrace.breakdown(run["trace"])
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    times = [t for _, t in bd["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert all(isinstance(name, str) for name, _ in bd["idle_gaps"])


def test_record_trace_cuts_to_the_first_steps(run):
    import record_trace

    steps = sorted(h[1] for h in run["trace"]["host"] if h[0] == "bench_step")
    cut = record_trace.cut(run, 1)
    kept = [h for h in cut["trace"]["host"] if h[0] == "bench_step"]
    assert len(kept) == 1 and kept[0][1] == steps[0]
    assert cut["rounds"] == pytest.approx(run["rounds"] / len(steps))
    assert 0 < cut["window_s"] <= run["window_s"]
    assert "peaks" not in cut
    assert read("device_idle_share", {**cut, "peaks": run["peaks"]}) \
        is not None
