"""Each cell's run, end to end on the CPU at four clients: the window
opens and closes inside the program's own period loop, the result line
has the contract's keys, and the output check passes."""
import io
import json

import pytest

from benchkit import CELLS, harness, tiny_cell  # noqa: F401

SEED = 2 ** 31 + 977


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_passes_its_check(harness, name):
    cell = tiny_cell(harness, name)
    err = io.StringIO()
    result = harness.run(cell, SEED, 0.2, False, check_out=err)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "checks"
    assert result["correct"], err.getvalue()
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"client_rounds_per_s",
                                      "period_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(result["device"])
    assert "compile events in window: 0" in err.getvalue()
    json.dumps(result)
    lines = err.getvalue().strip().splitlines()
    assert len(lines) >= len(result["checks"])
    assert all(line.startswith("check ")
               for line in lines[-len(result["checks"]):])
