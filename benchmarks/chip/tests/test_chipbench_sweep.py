"""The ``paper-mix2fld-sweep`` cell, cut to a CPU size (see ``chipbench_cases``)."""
import pytest

import chipbench_tiny as tiny

from chipbench_cases import *  # noqa: F401,F403  (the tests)


@pytest.fixture
def cell():
    return "paper-mix2fld-sweep"


def test_seed_prep_fault_is_not_correct(cell, monkeypatch):
    from chipbench_cases import swapped_seed_labels

    swapped_seed_labels(monkeypatch)
    result = tiny.run(cell)
    assert not result["correct"]
    assert result["checks"]["seed_label_errors"]["value"] > 0
