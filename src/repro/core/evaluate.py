"""Evaluation harness: per-client held-out metrics for every problem shape.

Closes the ROADMAP follow-up "population-level evaluation harness": one
module computes

  * cross-silo   -- per-client held-out error / mean loss for a single run's
                    final ``W`` (``evaluate_run``);
  * sweep grids  -- the same per-client table for every (regularizer,
                    shuffle) cell plus the (R, S) mean-error grid the
                    Table-1/4 protocol selects over (``evaluate_grid``);
  * cross-device -- per-cluster held-out-client evaluation: materialize
                    clients the run never (or least) trained on, score their
                    served weights (centroid + cached delta), and aggregate
                    by learned cluster (``evaluate_cohort``).

Every function returns an ``EvalReport`` -- the eval-table block of the
unified ``repro.api.Report`` -- so benchmark suites consume one schema
regardless of which execution path produced the run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dual import FederatedData
from repro.core.losses import Loss
from repro.utils.jax_compat import F32_DOT

Array = jax.Array

#: per-client metric columns the harness can compute
METRICS = ("error", "loss")


@dataclasses.dataclass
class EvalReport:
    """Held-out evaluation tables (the ``Report.evaluation`` block).

    ``per_client`` maps column name -> array over clients; single runs give
    ``(m,)`` columns, grids ``(R, S, m)``, cohort evaluations ``(n_holdout,)``
    (with a ``client`` id column).  ``per_cluster`` (cohort only) aggregates
    by LEARNED cluster.  ``grid`` (sweeps only) is the (R, S) mean held-out
    error used for model selection.  ``summary`` is flat scalars.
    """

    per_client: Dict[str, np.ndarray]
    per_cluster: Optional[Dict[str, np.ndarray]] = None
    grid: Optional[np.ndarray] = None
    summary: Dict[str, float] = dataclasses.field(default_factory=dict)


def _check_metrics(metrics: Tuple[str, ...]) -> Tuple[str, ...]:
    bad = [m for m in metrics if m not in METRICS]
    if bad:
        raise ValueError(f"unknown eval metrics {bad}; available: {METRICS}")
    return tuple(metrics)


@partial(jax.jit, static_argnums=(0,))
def _client_metrics(loss: Loss, W: Array, X: Array, y: Array,
                    mask: Array) -> Tuple[Array, Array]:
    """(error, mean loss) per client for one (m, d) weight matrix.

    The error column IS ``dual.per_task_error`` -- one definition of
    held-out error for the whole repo (sweep_errors, the benchmark
    baselines, and this harness must never disagree on it).
    """
    from repro.core.dual import per_task_error
    err = per_task_error(None, W, X, y, mask)
    z = jnp.einsum("tid,td->ti", X, W, precision=F32_DOT)
    cnt = jnp.maximum(jnp.sum(mask, axis=-1), 1.0)
    lval = jnp.sum(loss.value(z, y) * mask, axis=-1) / cnt
    return err, lval


def evaluate_run(W, holdout: FederatedData, loss: Loss,
                 metrics: Tuple[str, ...] = METRICS) -> EvalReport:
    """Per-client held-out table for a single run's final (m, d) weights."""
    metrics = _check_metrics(metrics)
    err, lval = _client_metrics(loss, jnp.asarray(W), holdout.X, holdout.y,
                                holdout.mask)
    table: Dict[str, np.ndarray] = {
        "client": np.arange(holdout.m),
        "n_holdout": np.asarray(holdout.n_t).astype(np.int64),
    }
    if "error" in metrics:
        table["error"] = np.asarray(err)
    if "loss" in metrics:
        table["loss"] = np.asarray(lval)
    summary = {}
    if "error" in metrics:
        summary["mean_error"] = float(np.mean(table["error"]))
    if "loss" in metrics:
        summary["mean_loss"] = float(np.mean(table["loss"]))
    return EvalReport(per_client=table, summary=summary)


@partial(jax.jit, static_argnums=(0,))
def _grid_client_metrics(loss, W, X, y, mask):
    over_shuffles = jax.vmap(partial(_client_metrics, loss),
                             in_axes=(0, 0, 0, 0))
    over_grid = jax.vmap(over_shuffles, in_axes=(0, None, None, None))
    return over_grid(W, X, y, mask)


def evaluate_grid(W, holdout: FederatedData, loss: Loss,
                  metrics: Tuple[str, ...] = METRICS) -> EvalReport:
    """Held-out tables for a (R, S, m, d) sweep result.

    ``holdout`` is the stacked (S, m, n, d) test split matching the sweep's
    shuffle axis.  The (R, S) ``grid`` of mean errors is what the Table-1/4
    protocol minimizes per shuffle.
    """
    metrics = _check_metrics(metrics)
    W = jnp.asarray(W)
    if W.ndim != 4 or holdout.X.ndim != 4:
        raise ValueError(
            f"evaluate_grid expects (R, S, m, d) weights and stacked "
            f"holdout; got {W.shape} and {holdout.X.shape}")
    err, lval = _grid_client_metrics(loss, W, holdout.X, holdout.y,
                                     holdout.mask)
    table: Dict[str, np.ndarray] = {}
    if "error" in metrics:
        table["error"] = np.asarray(err)
    if "loss" in metrics:
        table["loss"] = np.asarray(lval)
    grid = np.asarray(jnp.mean(err, axis=-1))
    best = grid.min(axis=0)        # best regularizer per shuffle
    summary = {
        "mean_error": float(grid.mean()),
        "best_mean_error": float(best.mean()),
        "best_stderr": float(best.std() / np.sqrt(max(len(best), 1))),
    }
    return EvalReport(per_client=table, grid=grid, summary=summary)


#: domain-separation tag for the held-out-client draw (never shares raw
#: draws with the schedule / population / rates streams)
_HOLDOUT_STREAM = 0x65766C   # "evl"


def holdout_client_ids(m: int, n_clients: int, seed: int,
                       participation: Optional[np.ndarray] = None
                       ) -> np.ndarray:
    """Deterministic held-out client sample for population evaluation.

    Prefers clients the run NEVER trained on (``participation == 0``);
    falls back to the full population when coverage was total.  Pure in
    ``(m, n_clients, seed, participation)`` so two invocations of a run
    evaluate identical clients.
    """
    rng = np.random.default_rng(
        np.random.SeedSequence([_HOLDOUT_STREAM, int(seed)]))
    pool = np.arange(m)
    if participation is not None:
        unseen = np.flatnonzero(np.asarray(participation) == 0)
        if unseen.size >= min(n_clients, 1):
            pool = unseen
    n = int(min(n_clients, pool.size))
    return np.sort(rng.choice(pool, size=n, replace=False))


def evaluate_cohort(pop, relationship, loss: Loss, n_clients: int,
                    seed: int = 0,
                    participation: Optional[np.ndarray] = None,
                    metrics: Tuple[str, ...] = METRICS) -> EvalReport:
    """Per-cluster held-out-client evaluation of a cross-device run.

    Materializes ``n_clients`` held-out clients (bit-reproducibly, preferring
    never-trained ones), scores each against its SERVED weights -- exactly
    what the online tier would answer: the eval goes through a
    ``repro.serve.store.ServedSnapshot`` of the relationship state, so the
    resolution rule (cluster centroid + cached personal delta; bare
    centroid for cold clients) has ONE source of truth shared with
    ``repro.serve.predict`` -- and aggregates by learned cluster assignment.
    """
    from repro.serve.store import ServedSnapshot  # runtime-lazy: serve sits
    # above core in the layering; the eval is a CONSUMER of the serve tier
    metrics = _check_metrics(metrics)
    ids = holdout_client_ids(pop.m, n_clients, seed, participation)
    if ids.size == 0:
        return EvalReport(per_client={"client": ids},
                          summary={"holdout_clients": 0.0})
    snap = ServedSnapshot.from_state(relationship)
    W = snap.client_weights(ids)
    errs = np.empty(ids.size)
    lvals = np.empty(ids.size)
    sizes = np.empty(ids.size, np.int64)
    for i, t in enumerate(ids):
        blk = pop.client_block(int(t))
        z = blk.X @ W[i]
        errs[i] = float(np.mean(np.sign(z) != np.sign(blk.y)))
        lvals[i] = float(jnp.mean(loss.value(jnp.asarray(z),
                                             jnp.asarray(blk.y))))
        sizes[i] = blk.n
    clusters = np.asarray(snap.assign)[ids]
    table: Dict[str, np.ndarray] = {"client": ids, "cluster": clusters,
                                    "n_holdout": sizes}
    if "error" in metrics:
        table["error"] = errs
    if "loss" in metrics:
        table["loss"] = lvals
    uniq = np.unique(clusters)
    per_cluster: Dict[str, np.ndarray] = {
        "cluster": uniq,
        "n_clients": np.asarray([(clusters == c).sum() for c in uniq]),
    }
    if "error" in metrics:
        per_cluster["mean_error"] = np.asarray(
            [errs[clusters == c].mean() for c in uniq])
    if "loss" in metrics:
        per_cluster["mean_loss"] = np.asarray(
            [lvals[clusters == c].mean() for c in uniq])
    summary = {"holdout_clients": float(ids.size)}
    if "error" in metrics:
        summary["mean_error"] = float(errs.mean())
    if "loss" in metrics:
        summary["mean_loss"] = float(lvals.mean())
    return EvalReport(per_client=table, per_cluster=per_cluster,
                      summary=summary)
