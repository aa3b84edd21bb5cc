"""The chip benchmark's yardstick on the CPU: FLOP and byte counts
against hand counts, the peaks table, the comparison that decides
``correct``, the data generator, and lookup of cells by name."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

from chipbench import checks, flops, harness, peaks  # noqa: E402

PAPER = json.loads((CHIP / "configs" / "paper-cnn-digits.json").read_text())


def test_cnn_flops_match_hand_count():
    # conv1: 28*28 outputs x 14 channels x 3*3*1 taps, 2 FLOPs a MAC
    conv1 = 2 * 28 * 28 * 14 * 9
    # conv2 after one 2x2 pool: 14*14 outputs x 20 channels x 3*3*14 taps
    conv2 = 2 * 14 * 14 * 20 * 126
    fc = 2 * 980 * 10                       # 7*7*20 = 980 inputs
    assert flops.cnn_layer_flops(PAPER) == {"conv1": 197_568,
                                            "conv2": 987_840, "fc": 19_600}
    assert (conv1, conv2, fc) == (197_568, 987_840, 19_600)
    fwd, fwd_bwd = flops.cnn_flops(PAPER)
    assert fwd == 1_205_008
    # backward: every weight gradient, input gradients but conv1's
    assert fwd_bwd == 2 * 1_205_008 + 987_840 + 19_600 == 3_417_456
    # the 12,490 weights the flops are taken over
    c1, c2 = PAPER["conv_channels"]
    assert 9 * c1 + c1 + 9 * c1 * c2 + c2 + 980 * 10 + 10 == \
        PAPER["n_params"] == 12_490


def test_paper_round_flops():
    # 10 devices x 200 steps x 16 samples, 160 x 16 conversion samples,
    # 1000 test samples forward: about 119 GFLOP
    want = 10 * 200 * 16 * 3_417_456 + 160 * 16 * 3_417_456 + \
        1000 * 1_205_008
    assert flops.round_flops(PAPER, trained_devices=10, convert=True) == want
    assert want == 119_312_287_360
    assert flops.round_flops(PAPER, trained_devices=10, convert=False,
                             points=8) == 8 * (want - 160 * 16 * 3_417_456)


def test_distill_kernel_cost_hand_count():
    # (devices, batch, classes) = (10, 16, 10): one launch over 160 rows
    cost = flops.distill_kernel_cost(10 * 16, 10)
    # forward reads logits and KD rows (2 x 160 x 10 f32), labels
    # (160 int32), writes phi and psi (2 x 160 f32)
    assert cost["fwd"]["bytes"] == 4 * (2 * 1600 + 160 + 2 * 160) == 14_720
    # backward also reads both cotangents and writes dz and dg
    assert cost["bwd"]["bytes"] == 4 * (2 * 1600 + 160 + 2 * 160 +
                                        2 * 1600) == 27_520
    assert cost["fwd"]["ops"] == 160 * (10 * 10 + 5) == 16_800
    assert cost["bwd"]["ops"] == 160 * (15 * 10 + 3) == 24_480


def test_peaks_by_device_kind():
    row = peaks.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99")


def _record(loss, norms, gout):
    return {"loss": loss, "acc": [0.5] * len(loss),
            "uplinks": [10] * len(loss), "update_norms": norms,
            "change_norms": norms, "gout": np.asarray(gout)}


def test_compare_reads_worst_leaf_and_skips_rounding_leaves():
    ref = _record([2.0, 1.0], {"a": 1.0, "b": 2.0, "c": 3.0, "z": 1e-6},
                  [[0.5]])
    prog = _record([2.0, 1.01], {"a": 1.1, "b": 2.0, "c": 3.0, "z": 1.0},
                   [[0.25]])
    got = checks.compare([prog], [ref])
    assert got["loss_rel"] == pytest.approx(0.01)
    # "a" is off by 0.1 over max(1.0, median 1.5); "z" is nought to
    # rounding in the reference and left out
    assert got["update_rel"] == pytest.approx(0.1 / 1.5)
    assert got["gout_abs"] == pytest.approx(0.25)
    assert got["uplinks"] == 0.0
    ok, rows = checks.judge(got, {"loss_rel": 0.02, "gout_abs": 0.1})
    assert not ok and rows["loss_rel"]["value"] <= rows["loss_rel"]["limit"]
    # a number with a limit that the run did not produce fails
    ok, rows = checks.judge({}, {"loss_rel": 1.0})
    assert not ok and rows["loss_rel"]["value"] == float("inf")


def test_compare_fails_nan():
    ref = _record([1.0], {"a": 1.0}, [[0.5]])
    prog = _record([float("nan")], {"a": 1.0}, [[0.5]])
    assert checks.compare([prog], [ref])["loss_rel"] == float("inf")


def _uploads_and_pair(lam=0.1):
    """Four uploads over three devices: (0 mixes into 1) on device 0,
    (1 into 0) on device 1, and two more; the server's set is the
    inverse of the symmetric pair, then a 3-cycle is not there."""
    rng = np.random.default_rng(0)
    u, v = rng.random((2, 6))
    up = np.stack([lam * u + (1 - lam) * v, lam * v + (1 - lam) * u,
                   rng.random(6), rng.random(6)])
    uploads = {"x": up, "minor": np.array([0, 1, 2, 3]),
               "major": np.array([1, 0, 3, 2]), "lam": lam, "want": 2}
    seeds = {"uploaded": up.copy(), "train_x": np.stack([u, v]),
             "train_y": np.array([0, 1]), "groups": [2]}
    return seeds, uploads


def test_seed_numbers_pass_an_inverse_mixup_pair():
    seeds, uploads = _uploads_and_pair()
    got = checks.seed_numbers(seeds, uploads)
    assert got["seed_upload_abs"] == 0.0
    assert got["seed_remix_abs"] < 1e-12
    assert got["seed_label_errors"] == 0.0


@pytest.mark.parametrize("fault", ["labels", "sample", "count", "upload"])
def test_seed_numbers_catch_a_wrong_seed_set(fault):
    seeds, uploads = _uploads_and_pair()
    if fault == "labels":
        seeds["train_y"] = seeds["train_y"][::-1].copy()
    elif fault == "sample":
        seeds["train_x"] = seeds["train_x"] * 1.01
    elif fault == "count":
        seeds["train_x"] = np.concatenate([seeds["train_x"]] * 2)
        seeds["train_y"] = np.concatenate([seeds["train_y"]] * 2)
        seeds["groups"] = [2, 2]
    else:
        seeds["uploaded"] = seeds["uploaded"] + 1e-3
    got = checks.seed_numbers(seeds, uploads)
    limits = {"seed_upload_abs": 1e-4, "seed_remix_abs": 1e-4,
              "seed_label_errors": 0}
    assert not checks.judge(got, limits)[0], got


def test_population_is_seeded_and_shares_prototypes():
    import jax

    from chipbench import traffic

    key = jax.random.PRNGKey(harness.program_seed(2 ** 31 + 12345))
    a = traffic.population(key, 3, 8, 16, 10, 28, 0.35, 2)
    b = traffic.population(key, 3, 8, 16, 10, 28, 0.35, 2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    dev_x, dev_y, test_x, test_y = a
    assert dev_x.shape == (3, 8, 28, 28, 1) and dev_y.shape == (3, 8)
    assert test_x.shape == (16, 28, 28, 1)
    assert float(dev_x.min()) > 0.0 and float(dev_x.max()) < 1.0
    assert harness.program_seed(5) != harness.program_seed(2 ** 33 + 5)
    assert 0 <= harness.program_seed(2 ** 40) < 2 ** 31


def test_committed_cells_are_found_by_name():
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        spec = harness.cell_spec(w["name"])
        assert spec["config"]["name"] == w["config"]
        assert (CHIP / "drivers" /
                f"{spec['workload']['driver']}.py").exists()
        limits = set(spec["workload"]["limits"])
        assert "uplinks" in limits and limits & {"loss_rel", "loss_abs"}
    for m in bench["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert mod.UNIT == m["unit"]


FAKE_DRIVER = '''
class Cell:
    failed = 0
    flops_per_round = 1.0
    programs = {}
    kernel = None

    def __init__(self, ctx):
        self.ctx = ctx
        self.rounds = 0

    def step(self):
        self.rounds += 1
        return 1

    def sync(self):
        pass

    def release(self):
        pass

    def program_records(self):
        return [{"loss": [1.0], "acc": [0.5], "uplinks": [3]}]

    def reference_records(self):
        loss = self.ctx.traffic["reference_loss"]
        return [{"loss": [loss], "acc": [0.5], "uplinks": [3]}]


def setup(ctx):
    return Cell(ctx)
'''


def test_added_workload_is_found_by_name_and_run(tmp_path):
    """A cell that a later change adds as files only: its configuration,
    traffic, workload and driver are found by the names in
    BENCHMARK.json, and the harness runs it end to end."""
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    bench["paths"] = ["bench"]
    bench["configs"] = [{"name": "cfg", "source": "x",
                         "file": "bench/configs/cfg.json", "reduced": [],
                         "why": "x"}]
    bench["workloads"] = [{"name": "new-cell", "config": "cfg",
                           "traffic": "mix", "chips": 1, "why": "x"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub, name, body in (
            ("configs", "cfg.json", json.dumps({"name": "cfg"})),
            ("traffic", "mix.json", json.dumps({"reference_loss": 1.0})),
            ("workloads", "new-cell.json", json.dumps(
                {"driver": "fake", "limits": {"loss_rel": 1e-6,
                                              "uplinks": 0}})),
            ("drivers", "fake.py", FAKE_DRIVER)):
        (tmp_path / "bench" / sub).mkdir(parents=True, exist_ok=True)
        (tmp_path / "bench" / sub / name).write_text(body)
    spec = harness.cell_spec("new-cell", root=tmp_path)
    assert spec["traffic"] == {"reference_loss": 1.0}
    code, res = harness.run_cell("new-cell", 1, 0.05, False, t_start=0.0,
                                 require_chip=False, spec=spec)
    assert code == 0 and res["correct"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}
    assert list(res["checks"]) == ["loss_rel", "uplinks",
                                   "programs_lowered_in_window"]
    spec["traffic"]["reference_loss"] = 1.5      # the check must bite
    _, res = harness.run_cell("new-cell", 1, 0.05, False, t_start=0.0,
                              require_chip=False, spec=spec)
    assert not res["correct"]
