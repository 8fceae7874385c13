"""Output check of `fleet-paper20-ipm05` on the CPU at a small size: the sound run is
correct; the control and every planted fault of the timed path are not."""
import pytest

from bench.tests import checks


@pytest.fixture(scope="module")
def cell():
    return checks.small_cell("fleet-paper20-ipm05")


def test_sound_run_is_correct(cell):
    line = checks.run_line(cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_control_fails_a_limit(cell):
    judged = checks.control_checks(cell)
    assert not all(c["ok"] for c in judged.values()), judged


@pytest.mark.parametrize("fault", checks.FAULTS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    with checks.broken(fault, monkeypatch):
        line = checks.run_line(cell)
    assert line["correct"] is False, (fault, line["checks"])
