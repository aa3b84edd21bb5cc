"""The ``paper-mix2fld-loop`` cell, cut to a CPU size (see ``chipbench_cases``)."""
import os
import subprocess
import sys

import pytest

import chipbench_tiny as tiny

from chipbench import checks  # noqa: E402

from chipbench_cases import *  # noqa: F401,F403  (the tests)


@pytest.fixture
def cell():
    return "paper-mix2fld-loop"


def test_control_fails_and_program_passes(cell):
    """The control and the half-batch fault, replayed at the CPU size,
    fail the committed limits; the program passes them."""
    import control

    rows = control.readings(cell, [3], 1, require_chip=False,
                            spec=tiny.spec(cell))
    limits = tiny.spec(cell)["workload"]["limits"]
    by_run = {r["run"]: r for r in rows}
    assert checks.judge(by_run["program"], limits)[0]
    assert not checks.judge(by_run["control_bf16"], limits)[0]
    assert not checks.judge(by_run["fault_half_batch"], limits)[0]


def test_run_refuses_without_a_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(tiny.CHIP / "run.py"), "--workload",
         "paper-mix2fld-loop", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tiny.CHIP.parents[1], timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """In a directory that holds BENCHMARK.json and the benchmark's
    files but not the program, the command fails and prints no result."""
    import shutil

    shutil.copy(tiny.CHIP.parents[1] / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", ".cache",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "paper-mix2fld-loop", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env={**env, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path, timeout=120)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


def test_seed_prep_fault_is_not_correct(cell, monkeypatch):
    from chipbench_cases import swapped_seed_labels

    swapped_seed_labels(monkeypatch)
    result = tiny.run(cell)
    assert not result["correct"]
    assert result["checks"]["seed_label_errors"]["value"] > 0
