"""The control: the plain reference computed in bfloat16, put in the
program's place, has to fail the output check of every cell."""
import pytest

from benchkit import CELLS, check_control_fails, harness  # noqa: F401


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_fails_the_check(harness, name):
    check_control_fails(harness, name)
