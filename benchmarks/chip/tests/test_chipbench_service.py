"""The ``population-fd-service`` cell, cut to a CPU size (see ``chipbench_cases``)."""
import pytest

from chipbench_cases import *  # noqa: F401,F403  (the tests)


@pytest.fixture
def cell():
    return "population-fd-service"
