"""A run whose timed path is broken underneath has to come out not
correct: once for each fault a federation cell can have. The program's
own functions are patched for the test, so the harness drives the
broken program exactly as it drives the sound one."""
import io

import pytest

from benchkit import CELLS, harness, tiny_cell  # noqa: F401


def unchanged_state(monkeypatch):
    """The local update returns the parameters and optimizer state it
    was given."""
    from repro.core import protocol
    orig = protocol.batched_local_update

    def update(apply_fn, optimizer, fed, params, opt_state, *rest):
        _, _, metrics = orig(apply_fn, optimizer, fed, params, opt_state,
                             *rest)
        return params, opt_state, metrics
    monkeypatch.setattr(protocol, "batched_local_update", update)


def half_batch(monkeypatch):
    """Each local minibatch's loss is the mean over its first half."""
    from repro.core import distill
    orig = distill.combined_loss

    def loss(apply_fn, params, batch, *rest, **kw):
        n = batch["x"].shape[0] // 2
        return orig(apply_fn, params,
                    {"x": batch["x"][:n], "y": batch["y"][:n]}, *rest, **kw)
    monkeypatch.setattr(distill, "combined_loss", loss)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(harness, monkeypatch, name,
                                          fault):
    fault(monkeypatch)
    cell = tiny_cell(harness, name)
    result = harness.run(cell, 2 ** 31 + 7, 0.2, False,
                         check_out=io.StringIO())
    assert result["correct"] is False
    assert result["failed"] >= 1
