"""Each cell's run, end to end on the CPU at four clients: the window
opens and closes inside the program's own period loop, the result line
has the contract's keys, and the output check passes."""
import pytest

from benchkit import CELLS, check_cell_runs, harness  # noqa: F401


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_passes_its_check(harness, name):
    check_cell_runs(harness, name)
