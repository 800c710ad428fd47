"""Faults planted in the program's timed path, to show that ``correct``
catches them.

Each is a context manager that patches the program underneath the
benchmark for the duration of the block; the harness, the window and the
comparison run unchanged:

* ``unchanged_state`` -- every W-round returns the dual state it was given;
* ``half_batch``      -- the second half of the tasks is left out of every
                         round (their step budgets are zero), the round
                         goes on with the rest;
* ``altered_answer``  -- the job's answer is altered where it is produced:
                         the first task's final weights change sign.

The benchmark's own runs never use them: ``tests/test_faults.py`` (on the
CPU) and ``readings.py`` (on the chip) do.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, Iterator

import jax.numpy as jnp


def _unchanged_round(loss, max_steps, gram, data, state, K, q_t, budgets,
                     gamma, key):
    return state


def _half_batch_round(loss, max_steps, gram, data, state, K, q_t, budgets,
                      gamma, key):
    from repro.core.engine import _local_round
    keep = jnp.arange(budgets.shape[0]) < budgets.shape[0] // 2
    return _local_round(loss, max_steps, gram, data, state, K, q_t,
                        jnp.where(keep, budgets, 0), gamma, key)


@contextlib.contextmanager
def _round_fn(fn: Callable) -> Iterator[None]:
    from repro.core.engine import LocalEngine
    saved = LocalEngine.scan_round_fn
    LocalEngine.scan_round_fn = lambda self: fn
    try:
        yield
    finally:
        LocalEngine.scan_round_fn = saved


def unchanged_state():
    return _round_fn(_unchanged_round)


def half_batch():
    return _round_fn(_half_batch_round)


@contextlib.contextmanager
def altered_answer() -> Iterator[None]:
    from repro.core import mocha
    saved = mocha._run_scanned

    def run(*args, **kwargs):
        res = saved(*args, **kwargs)
        W = res.W.copy()
        W[0] = -W[0]
        return dataclasses.replace(res, W=W)

    mocha._run_scanned = run
    try:
        yield
    finally:
        mocha._run_scanned = saved


FAULTS: Dict[str, Callable] = {"unchanged_state": unchanged_state,
                               "half_batch": half_batch,
                               "altered_answer": altered_answer}
