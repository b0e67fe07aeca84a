"""The correctness check of ``scrna-l1.fit`` at a test size, on the CPU:
the sound program passes the cell's limits, and the control and the SWAP
fault turn ``correct`` false.  Driven as ``test_control.py`` drives the
cells of its ``SIZES``, whose helpers it shares; n is cut to 1,000 (the
reference's exact l1 SWAP check grows as n² d).
"""

import pytest
from test_control import SEED

from bench import control, harness

NAME = "scrna-l1.fit"
SIZE = {"n": 1000}
_sound = {}


def _cell():
    return harness.Cell(NAME, shrink=SIZE)


def _sound_run():
    if "res" not in _sound:
        _sound["res"] = control.read(_cell(), SEED, 0.5, "program")
    return _sound["res"]


def test_sound_program_is_correct():
    res = _sound_run()
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("what", ["control", "swap_unchanged"])
def test_control_and_swap_fault_are_not_correct(what):
    res = control.read(_cell(), SEED, 0.5, what)
    assert not res["correct"], res["checks"]
    sound = _sound_run()["checks"]
    caught = [k for k, c in res["checks"].items()
              if (c["value"] == "inf" or c["value"] > c["limit"])
              and sound[k]["value"] <= c["limit"]]
    assert caught, (res["checks"], sound)
