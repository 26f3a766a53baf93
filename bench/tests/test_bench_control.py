"""The control: the plain reference computed in bfloat16, put in the
program's place, has to fail the output check of every cell."""
import pytest

from benchkit import CELLS, harness, tiny_cell  # noqa: F401

import compare
import control


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_fails_the_check(harness, name):
    cell = tiny_cell(harness, name)
    found = control.readings(cell, 2 ** 31 + 51, kinds=("control",))
    checks = compare.judge(found["control"], cell["limits"])
    assert checks
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
