"""The correctness check at a test size, on the CPU: the sound program
passes each cell's limits, and the control and every planted fault the
cell can have turn ``correct`` false.  The harness's look for a chip is
skipped; everything else of a run is driven as ``run.py`` drives it.

The sizes are the smallest at which the sound program passes the
cell's limits: the l2 loss of a small data set carries the medoids' own
rounded distances (``sqrt`` of the norm expansion's residue, ~3e-3
each), which the cell's full size dilutes.
"""

import pytest

from bench import control, datagen, harness

SIZES = {
    "mnist-l2.fit": {"n": 5000},
}
SEED = 2**32 + 11
FAULTS = ("control", "swap_unchanged", "half_batch", "altered")


def _cell(name):
    return harness.Cell(name, shrink=SIZES[name])


CELLS = [n for n in sorted(SIZES) if n in harness.Cell.names()]
_sound = {}


def _sound_run(name):
    if name not in _sound:
        _sound[name] = control.read(_cell(name), SEED, 0.5, "program")
    return _sound[name]


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    res = _sound_run(name)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_solver_seed_per_run_fits_the_drawn_seed(name):
    cell = _cell(name)
    res = control.read(cell, SEED, 0.5, "program", solver_seed_per_run=True)
    assert cell.traffic["solver_seeds"] == [datagen.fit_seed(SEED)]
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name,what", [
    (name, what) for name in CELLS for what in FAULTS])
def test_control_and_faults_are_not_correct(name, what):
    res = control.read(_cell(name), SEED, 0.5, what)
    assert not res["correct"], res["checks"]
    sound = _sound_run(name)["checks"]
    caught = [k for k, c in res["checks"].items()
              if (c["value"] == "inf" or c["value"] > c["limit"])
              and sound[k]["value"] <= c["limit"]]
    assert caught, (res["checks"], sound)
