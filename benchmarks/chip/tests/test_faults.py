"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, comparison) at a small size on the CPU, once sound and
once with each fault of ``faults.py`` planted in the program.  No cell runs
on more than one chip, so the fault of a left-out exchange between chips
cannot arise.
"""
import contextlib

import pytest

from benchmarks.chip import faults
from benchmarks.chip.tests.cells import SMALL, small_run


def _run(cell):
    doc = small_run(cell)
    assert doc["attempted"] > 0 and doc["failed"] == 0
    return doc


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    doc = _run(cell)
    assert doc["correct"], doc["checks"]


@contextlib.contextmanager
def _altered_margins():
    from repro.serve import predict
    saved = predict._margins

    def altered(*args):
        return saved(*args).at[0].add(1.0)

    predict._margins = altered
    try:
        yield
    finally:
        predict._margins = saved


_PLANTS = dict(faults.FAULTS)
_SERVE_PLANTS = dict(faults.FAULTS, altered_answer=_altered_margins)


@pytest.mark.parametrize("cell", sorted(SMALL))
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(cell, fault):
    plants = _SERVE_PLANTS if cell == "xdev.serve" else _PLANTS
    with plants[fault]():
        doc = _run(cell)
    assert not doc["correct"], doc["checks"]
