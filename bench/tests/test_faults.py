"""Each cell's run, at the configuration's rehearsal sizes on the CPU,
with the harness's look for a chip skipped: sound, it is correct; with
each fault the cell can have planted in the timed path, it is not.

    python -m pytest bench/tests
"""

import copy

import jax
import pytest

from harness import cell as cellmod
from harness import faults, spec

CELLS = spec.workload_names()


def rehearsal(name):
    cell = spec.load_cell(name)
    cell = copy.copy(cell)
    cell.config = {**cell.config, **cell.config["rehearsal"]}
    return cell


def run(cell):
    return cellmod.run(cell, 7, 0.2, False, jax.devices()[:cell.chips],
                       on_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(rehearsal(name))
    assert out["correct"], out["compared"]
    assert "metrics" not in out


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS
    for fault in faults.faults_for(spec.load_cell(name))])
def test_fault_is_not_correct(name, fault, monkeypatch):
    cell = rehearsal(name)
    planted = []
    init = cellmod.Job.__init__

    def broken_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        ctx = faults.FAULTS[fault](self)
        ctx.__enter__()
        planted.append(ctx)

    monkeypatch.setattr(cellmod.Job, "__init__", broken_init)
    try:
        out = run(cell)
    finally:
        for ctx in planted:
            ctx.__exit__(None, None, None)
    assert planted
    assert not out["correct"], out["compared"]
