"""The Vehicle Sensor cell, ``vs.full``, on the CPU.

A small run of the cell (m=3 at the published d=100, every task with at
least 128 training points so that the solver takes the compact
accumulator) comes out correct, and in the SDCA solver's gram residual
mode; with each fault of ``faults.py`` planted it comes out not correct.
At the cell's published size the lower-precision control fails the cell's
limits, and the published shapes take the gram mode with chunks of 32.
"""
import pytest

from benchmarks.chip import compare, faults, federation, reference, run
from benchmarks.chip.tests.cells import benchmark

CELL = "vs.full"
SMALL = ({"m": 3, "d": 100, "n_min": 172, "n_max": 200},
         {"rounds": 6, "trace_seconds": 1})
SEEDS = (2**31 + 11, 2**31 + 12, 2**31 + 13)


def _small_run(seed: int = 2**31 + 5):
    bench = benchmark()
    cell = run.make_cell(bench, CELL, seed, 1.0, False)
    cfg, traffic = SMALL
    cell.config.update(cfg)
    cell.traffic.update(traffic)
    doc = run.run_cell(cell, require_chip=False, bench=bench)
    assert doc["attempted"] > 0 and doc["failed"] == 0
    return doc


@pytest.fixture
def gram_modes(monkeypatch):
    """The residual mode of every job the run makes, from its report."""
    from repro.api import Experiment
    modes, real = [], Experiment.run

    def recorded(self, *args, **kwargs):
        report = real(self, *args, **kwargs)
        modes.append(report.provenance["gram_mode"])
        return report

    monkeypatch.setattr(Experiment, "run", recorded)
    return modes


def test_sound_run_is_correct_in_gram_mode(gram_modes):
    doc = _small_run()
    assert doc["correct"], doc["checks"]
    assert gram_modes and set(gram_modes) == {"gram"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(fault):
    with faults.FAULTS[fault]():
        doc = _small_run()
    assert not doc["correct"], doc["checks"]


def test_published_shapes_take_gram_chunks_of_32():
    from repro.core.subproblem import _solver_plan
    cell = run.make_cell(benchmark(), CELL, 0, 0.0, False)
    train, test = federation.make_federation(cell.config, SEEDS[0])
    _, n, d = train[0].shape
    assert (d, n, test[0].shape[1]) == (100, 1449, 484)
    steps = reference.max_steps(cell.traffic["budget"], n)
    assert _solver_plan(d, steps) == (True, 32)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails(seed):
    cell = run.make_cell(benchmark(), CELL, 0, 0.0, False)
    cfg, tr = cell.config, cell.traffic
    train, test = federation.make_federation(cfg, seed)
    ref = reference.run_job(cfg, tr, train, test, seed)
    ctl = reference.run_job(cfg, tr, train, test, seed, precision="high")
    ok, checks = compare.judge(compare.job_readings(ctl, ref),
                               compare.load_limits(CELL))
    assert not ok, checks

