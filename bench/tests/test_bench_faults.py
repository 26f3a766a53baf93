"""A run whose timed path is broken underneath has to come out not
correct: once for each fault a federation cell can have (benchkit's
FAULTS: a step that returns its state unchanged, half of each local
batch left out). The program's own functions are patched for the
test, so the harness drives the broken program exactly as it drives
the sound one."""
import pytest

from benchkit import CELLS, FAULTS, check_fault_fails, harness  # noqa: F401


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(harness, monkeypatch, name,
                                          fault):
    check_fault_fails(harness, monkeypatch, name, fault)
