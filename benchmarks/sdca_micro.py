"""SDCA inner-loop micro-benchmark: v1 (pre-Gram) vs v2 carry/gram per-step
cost across (n, d, C) shapes, plus the roofline artifacts the report
consumes.

Three timed variants per shape, driven with IDENTICAL coordinate streams:

  * ``v1``    -- frozen copy of the pre-rewrite dense loop (two length-d
                 reductions + axpy per step, per-step (n,) dual scatter,
                 per-round xnorm recompute): the seed-solver baseline;
  * ``carry`` -- arithmetic v2 with the residual mode forced to carry;
  * ``gram``  -- arithmetic v2 with the residual mode forced to gram.

One of carry/gram is the PRODUCTION row (whatever the static
``_solver_plan`` rule picks for the shape).  Measurements interleave the
variants round-robin and keep the per-variant minimum, so machine noise
hits every variant equally.  The quick grid gates CI: a production-row
``speedup_vs_v1`` below 1.0 raises (benchmarks/run.py exits non-zero).

For every shape the production and v1 loops are also costed with XLA's
HLO cost analysis and written as ``results/roofline/sdca_*.json`` -- the
rows ``benchmarks/roofline_report.py`` previously only had a placeholder
for.  XLA counts a while-loop body ONCE regardless of trip count, so the
probes compile python-unrolled loops at two depths and difference them
(the same methodology as launch/roofline.py's depth differencing), then
extrapolate to the real step count: per_unit = (C(k2) - C(k1))/(k2 - k1),
full = C(k1) + (real - k1) * per_unit.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import get_loss
from repro.core.subproblem import (_solver_plan,
                                   local_sdca_idx, row_norms)
from repro.utils.jax_compat import fp_barrier
from repro.utils.timing import tick

ROOFLINE_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                            "roofline")

# TPU v5e roofline constants (mirrors repro.launch.roofline; duplicated so
# importing this module never triggers that module's XLA_FLAGS side effects)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

HINGE = get_loss("hinge")

#: (tag, m, n, d, steps) -- d spans both sides of the _GRAM_MAX_D crossover;
#: ha/vs mirror the paper's Human Activity / Vehicle Sensor shapes
QUICK_SHAPES = [
    ("ha_like", 30, 512, 561, 512),
    ("vs_like", 10, 1000, 100, 1000),
    ("lowd", 8, 2000, 48, 1024),
]
FULL_SHAPES = QUICK_SHAPES + [
    ("ha_full", 30, 512, 561, 1024),
    ("pooled", 4, 8192, 561, 2048),
    ("gg_like", 20, 560, 180, 560),
]


def _v1_dense_loop(loss, X, y, mask, alpha, w, q, budget, idx, max_steps,
                   unroll=False):
    """Frozen pre-rewrite (arithmetic v1) dense inner loop, barriers and
    per-round xnorm recompute included -- the honest seed baseline.  ALSO
    the v1 reference of tests/test_subproblem.py's convergence-equivalence
    regression: one frozen copy, imported from here.  ``unroll`` runs the
    (pure) step body as a python loop for the HLO cost probes."""
    n = X.shape[0]
    xnorm2 = jnp.sum(X * X, axis=1)

    def body(s, carry):
        dalpha, u = carry
        i = idx[s]
        x = X[i]
        a = alpha[i] + dalpha[i]
        g_dot_x = jnp.sum(x * w) + fp_barrier(q * jnp.sum(x * u))
        delta = loss.sdca_delta(a, y[i], g_dot_x, q * xnorm2[i])
        live = ((s < budget) & (mask[i] > 0)).astype(delta.dtype)
        delta = delta * live
        return dalpha.at[i].add(delta), u + fp_barrier(delta * x)

    carry = (jnp.zeros(n), jnp.zeros(X.shape[1]))
    if unroll:
        for s in range(max_steps):
            carry = body(s, carry)
        return carry
    return jax.lax.fori_loop(0, max_steps, body, carry)


def _make_problem(m, n, d, steps, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(0, 1, (m, n, d)) / np.sqrt(d), jnp.float32)
    y = jnp.sign(jnp.asarray(rng.normal(0, 1, (m, n)), jnp.float32))
    mask = jnp.ones((m, n), jnp.float32)
    alpha = jnp.zeros((m, n), jnp.float32)
    W = jnp.asarray(rng.normal(0, 0.1, (m, d)), jnp.float32)
    q = jnp.full((m,), 0.7, jnp.float32)
    budgets = jnp.full((m,), steps, jnp.int32)
    idx = jnp.asarray(rng.integers(0, n, (m, steps)), jnp.int32)
    xn = jax.jit(row_norms)(X)
    return X, y, mask, alpha, W, q, budgets, idx, xn


def _variant_fns(steps):
    """jitted (v1, carry, gram) callables over the same argument tuple."""

    @jax.jit
    def v1(X, y, mask, alpha, W, q, budgets, idx, xn):
        fn = lambda X, y, ma, al, w, qq, b, i: _v1_dense_loop(
            HINGE, X, y, ma, al, w, qq, b, i, steps)
        return jax.vmap(fn)(X, y, mask, alpha, W, q, budgets, idx)

    def v2(gram):
        @jax.jit
        def f(X, y, mask, alpha, W, q, budgets, idx, xn):
            fn = lambda X, y, ma, al, w, qq, b, i, x2: local_sdca_idx(
                HINGE, X, y, ma, al, w, qq, b, i, steps, x2, gram)
            return jax.vmap(fn)(X, y, mask, alpha, W, q, budgets, idx, xn)
        return f

    return {"v1": v1, "carry": v2(False), "gram": v2(True)}


def _interleaved_times(fns: Dict, args, reps: int, iters: int) -> Dict:
    """Min-of-reps wall time per variant, variants interleaved round-robin
    so contention spikes hit all of them alike."""
    for f in fns.values():                       # compile + warm
        jax.block_until_ready(f(*args))
    best = {k: float("inf") for k in fns}
    for _ in range(reps):
        for k, f in fns.items():
            t0 = tick()
            for _ in range(iters):
                jax.block_until_ready(f(*args))
            best[k] = min(best[k], (tick() - t0) / iters)
    return best


def _hlo_cost(fn, args) -> Dict:
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):                   # older jax returns [dict]
        cost = cost[0] if cost else {}
    return {"flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes": float(cost.get("bytes accessed", 0.0) or 0.0)}


def _diffed_cost(probe, k1: int, k2: int, real_units: float,
                 args) -> Dict:
    """Depth-differenced full-loop HLO cost (XLA counts loop bodies once,
    so probes run python-unrolled at depths k1 < k2 and extrapolate)."""
    c1, c2 = _hlo_cost(probe(k1), args), _hlo_cost(probe(k2), args)
    out = {}
    for key in ("flops", "bytes"):
        per = (c2[key] - c1[key]) / (k2 - k1)
        out[key] = max(0.0, c1[key] + (real_units - k1) * per)
    return out


def _cost_terms(variant: str, steps: int, gram: bool, C: int, args) -> Dict:
    """Extrapolated per-call HLO FLOP/byte counts for a solve variant."""
    X, y, mask, alpha, W, q, budgets, idx, xn = args
    if variant == "v1":
        def probe(k):
            def f(X, y, mask, alpha, W, q, budgets, idx, xn):
                fn = lambda X, y, ma, al, w, qq, b, i: _v1_dense_loop(
                    HINGE, X, y, ma, al, w, qq, b, i, k, unroll=True)
                return jax.vmap(fn)(X, y, mask, alpha, W, q, budgets,
                                    idx[:, :k])
            return f
        return _diffed_cost(probe, 2 * C, 4 * C, steps, args)
    # v2: difference over unrolled CHUNK counts, extrapolate to n_chunks
    def probe(k):
        def f(X, y, mask, alpha, W, q, budgets, idx, xn):
            fn = lambda X, y, ma, al, w, qq, b, i, x2: local_sdca_idx(
                HINGE, X, y, ma, al, w, qq, b, i, k * C, x2, gram,
                unroll_chunks=True)
            return jax.vmap(fn)(X, y, mask, alpha, W, q, budgets,
                                idx[:, :k * C], xn)
        return f
    n_chunks = -(-steps // C)
    return _diffed_cost(probe, 2, 4, n_chunks, args)


def _write_roofline_artifact(tag, mode, m, n, d, steps, cost, v1_cost):
    os.makedirs(ROOFLINE_DIR, exist_ok=True)
    t_comp = cost["flops"] / PEAK_FLOPS
    t_mem = cost["bytes"] / HBM_BW
    # useful work: one g reduction + one update axpy per live step
    model_flops = 4.0 * d * steps * m
    rec = {
        "arch": f"sdca_{mode}", "shape": tag, "status": "ok",
        "m": m, "n": n, "d": d, "steps": steps,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": 0.0,
        "dominant": "compute" if t_comp >= t_mem else "memory",
        "model_flops": model_flops,
        "hlo_flops": cost["flops"], "hlo_bytes": cost["bytes"],
        "v1_hlo_flops": v1_cost["flops"], "v1_hlo_bytes": v1_cost["bytes"],
        "arithmetic_intensity": (cost["flops"] / cost["bytes"]
                                 if cost["bytes"] else 0.0),
        "useful_ratio": (model_flops / cost["flops"]
                         if cost["flops"] else 0.0),
    }
    path = os.path.join(ROOFLINE_DIR, f"sdca_{mode}__{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)


def run(quick: bool = True) -> List[Dict]:
    shapes = QUICK_SHAPES if quick else FULL_SHAPES
    reps, iters = (5, 2) if quick else (7, 3)
    rows: List[Dict] = []
    gate_failures = []
    # clear OUR old artifacts: roofline_report globs the whole directory, so
    # stale shapes/modes from earlier grids must not leak into the report
    import glob as _glob
    for stale in _glob.glob(os.path.join(ROOFLINE_DIR, "sdca_*.json")):
        os.remove(stale)
    for tag, m, n, d, steps in shapes:
        args = _make_problem(m, n, d, steps)
        fns = _variant_fns(steps)
        times = _interleaved_times(fns, args, reps, iters)
        gram_prod, C = _solver_plan(d, steps)
        prod_mode = "gram" if gram_prod else "carry"
        costs = {k: _cost_terms(k, steps, gram_prod, C, args)
                 for k in ("v1", prod_mode)}
        _write_roofline_artifact(tag, prod_mode, m, n, d, steps,
                                 costs[prod_mode], costs["v1"])
        for variant in ("v1", "carry", "gram"):
            t = times[variant]
            speedup = times["v1"] / t
            row = {
                "bench": "sdca", "shape": tag, "variant": variant,
                "m": m, "n": n, "d": d, "steps": steps, "C": C,
                "us_per_call": t * 1e6,
                "us_per_step": t * 1e6 / steps,
                "speedup_vs_v1": speedup,
                "production": variant == prod_mode,
            }
            if variant in costs:
                row["hlo_flops"] = costs[variant]["flops"]
                row["hlo_bytes"] = costs[variant]["bytes"]
            rows.append(row)
            if quick and variant == prod_mode and speedup < 1.0:
                gate_failures.append((tag, variant, speedup))
    if gate_failures:
        raise RuntimeError(
            "SDCA per-step speedup regression on the quick grid "
            f"(production new-vs-old < 1.0): {gate_failures}")
    return rows
