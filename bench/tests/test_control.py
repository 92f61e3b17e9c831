"""The control of ``correct``: the plain reference put in the program's
place at the precision below the configuration's (three bf16 passes)
fails the cell's limits, where the program's own first segment passes
them.  At the configurations' rehearsal sizes, which a CPU holds; the
readings at the cells' own sizes, on the chip, are
``bench/calibrate.py``'s (see PERF.md)."""

import copy

import jax
import pytest

import calibrate
from harness import spec


@pytest.mark.parametrize("name", spec.workload_names())
def test_control_fails_where_program_passes(name):
    cell = copy.copy(spec.load_cell(name))
    cell.config = {**cell.config, **cell.config["rehearsal"]}
    got = calibrate.readings(cell, 11, jax.devices()[:cell.chips],
                             control=True, faults=[])
    limits = cell.limits
    assert all(got["program"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got
