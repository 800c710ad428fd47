"""The lower-precision control comes out not correct.

The control is the plain reference computed at the next precision down
from the configuration's (bfloat16x3 products for float32 at HIGHEST, see
``reference.py``), put in the program's place and judged against the
reference by the cell's own limits, at the cell's own sizes and on the
CPU.  ``readings.py`` reads the same on the chip.
"""
import numpy as np
import pytest

from benchmarks.chip import compare, federation, reference, reference_cohort
from benchmarks.chip import run
from benchmarks.chip.tests.cells import benchmark

SEEDS = (2**31 + 11, 2**31 + 12, 2**31 + 13)


def _cell(name):
    return run.make_cell(benchmark(), name, 0, 0.0, False)


def _fails(name, readings):
    ok, checks = compare.judge(readings, compare.load_limits(name))
    assert not ok, checks


@pytest.mark.parametrize("seed", SEEDS)
def test_silo_control_fails(seed):
    name = "ha.full"
    cell = _cell(name)
    cfg, tr = cell.config, cell.traffic
    train, test = federation.make_federation(cfg, seed)
    ref = reference.run_job(cfg, tr, train, test, seed)
    ctl = reference.run_job(cfg, tr, train, test, seed, precision="high")
    _fails(name, compare.job_readings(ctl, ref))


@pytest.mark.parametrize("seed", SEEDS)
def test_cohort_control_fails(seed):
    cell = _cell("xdev.blocks")
    blocks = cell.traffic["blocks"]
    ref = reference_cohort.run_job(cell.config, seed, seed + 1, blocks)
    ctl = reference_cohort.run_job(cell.config, seed, seed + 1, blocks,
                                   precision="high")
    _fails("xdev.blocks", compare.cohort_readings(ctl, ref))


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails(seed):
    cell = _cell("xdev.serve")
    cfg, blocks = cell.config, 12
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg["m"], 256)
    X = (rng.normal(0.0, 1.0, (256, cfg["d"]))
         / np.sqrt(cfg["d"])).astype(np.float32)
    got, want = [], []
    for precision, out in (("high", got), ("highest", want)):
        versions = []
        reference_cohort.run_job(cfg, seed, seed + 1,
                                 cell.traffic["train_blocks"], precision,
                                 versions=versions, stop_after=blocks)
        out.extend(reference_cohort.margins(v, ids, X, precision)
                   for v in versions[1:])
    _fails("xdev.serve", compare.margin_readings(np.concatenate(got),
                                                 np.concatenate(want)))
