"""Smoke run of MOCHA's training and serving path on a TPU.

    python chip_smoke.py             # one chip: training, kernel, serving
    python chip_smoke.py --chips 4   # four chips: sharded engine only

Everything goes through ``repro.api.Experiment`` at the paper's full
federation widths (``repro.data.synthetic``), with data made from fixed
seeds.  One process, no child processes (a chip belongs to one process).

One-chip phases, each printing one line:

  * ``device``  -- JAX's first device must be a TPU;
  * ``train``   -- Human Activity (d=561, carry mode) and Vehicle Sensor
    (d=100, gram mode) on the default ``local`` engine and scan driver: the
    duality gap must be finite and fall, and the final ``W`` and held-out
    error must agree with the same experiment run on the host CPU;
  * ``kernel``  -- Human Activity on ``engine="pallas"``: the round's lowered
    program must hold the compiled Mosaic kernel (``tpu_custom_call``), and
    its results must agree with the ``local`` engine on the chip;
  * ``serve``   -- ``Experiment.serve`` over a 100,000-client population at
    ``benchmarks/serve_bench.py``'s shapes: predict batches answered while
    cohort blocks stream must match ``serve.store.resolve_weights`` on the
    host, with no retried or degraded block.

``--chips 4`` runs only the sharded engine (Human Activity, m=30 padded to
32 over a 4-chip ``data`` mesh) against the ``local`` engine on one chip.

``compile_s`` is a phase's first call minus the same call repeated warm;
``run_s`` is the warm call.  Tolerances are printed beside the observed
differences.  The last line of standard output is one JSON object naming
the device; it is printed only when every phase passed.  The exit code is
non-zero when JAX finds no TPU or any phase fails.

The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the host reference runs on JAX's CPU backend in this same process
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

ROUNDS = 20
OMEGA_EVERY = 10
#: max |W_a - W_b| / max |W_b|: the chip's reductions associate differently
#: from the host's, and SDCA compounds the last-bit differences over
#: ROUNDS x n sequential coordinate steps
TOL_W = 5e-4
#: |mean held-out error_a - error_b| (a fraction of test points)
TOL_ERR = 2e-3
#: max |margin_chip - margin_host| / max(1, max |margin_host|)
TOL_MARGIN = 1e-5
SERVE_BLOCKS = 6
SERVE_BATCH = 256
SERVE_PREDICTS = 8
SERVE_DEADLINE_S = 300.0


def _line(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _timed(fn):
    """(result of the warm call, compile_s, run_s): first call vs repeat."""
    from repro.utils.timing import tick
    t0 = tick()
    fn()
    first = tick() - t0
    t0 = tick()
    out = fn()
    run = tick() - t0
    return out, max(first - run, 0.0), run


def _experiment(spec, engine="local"):
    from repro.api import Eval, Exec, Experiment, Method, Problem
    from repro.core import Probabilistic
    from repro.data.synthetic import make_federation
    train, test = make_federation(spec, seed=0)
    return Experiment(
        problem=Problem(train=train),
        method=Method(loss="hinge",
                      regularizers=Probabilistic(lam=1e-2, sigma2=10.0),
                      rounds=ROUNDS, omega_update_every=OMEGA_EVERY),
        exec=Exec(engine=engine),
        eval=Eval(record_every=1, holdout=test))


def _w_diff(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def _err(report) -> float:
    return float(report.evaluation.summary["mean_error"])


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def phase_device(chips: int):
    dev = jax.devices()
    _check(dev[0].platform == "tpu",
           f"JAX's first device is {dev[0].platform!r}, not a TPU")
    _check(len(dev) >= chips, f"{len(dev)} devices, need {chips}")
    _line(phase="device", ok=True, platform=dev[0].platform,
          kind=repr(dev[0].device_kind), count=len(dev))


def phase_train(spec) -> None:
    report, compile_s, run_s = _timed(lambda: _experiment(spec).run(seed=0))
    gap = np.asarray(report.history["gap"], np.float64)
    _check(np.isfinite(gap).all(), f"non-finite duality gap {gap}")
    _check(gap[-1] < 0.5 * gap[0], f"gap did not fall: {gap[0]} -> {gap[-1]}")
    prov = report.provenance
    _check((prov["engine"], prov["driver"]) == ("local", "scan"),
           f"routed to {prov['engine']}/{prov['driver']}")
    with jax.default_device(jax.devices("cpu")[0]):
        ref = _experiment(spec).run(seed=0)
    dw = _w_diff(report.result.W, ref.result.W)
    derr = abs(_err(report) - _err(ref))
    _line(phase="train", fed=spec.name, mode=prov["gram_mode"],
          ok=dw <= TOL_W and derr <= TOL_ERR, compile_s=compile_s,
          run_s=run_s, gap_first=gap[0], gap_last=gap[-1],
          error=_err(report), tol_W=TOL_W, dW_vs_cpu=dw, tol_err=TOL_ERR,
          derr_vs_cpu=derr)
    _check(dw <= TOL_W, f"W differs from the CPU run by {dw} > {TOL_W}")
    _check(derr <= TOL_ERR,
           f"held-out error differs from the CPU run by {derr} > {TOL_ERR}")


def phase_kernel(spec) -> None:
    import jax.numpy as jnp

    from repro.core.engine import PallasEngine, _pallas_round
    engine = PallasEngine()
    report, compile_s, run_s = _timed(
        lambda: _experiment(spec, engine=engine).run(seed=0))
    _check(report.provenance["engine"] == "pallas", "not the pallas engine")
    m = engine.data.m
    lowered = _pallas_round.lower(
        engine.max_steps, engine._interpret, engine.gram, engine.data,
        report.result.state, jnp.eye(m),
        jnp.ones((m,)), jnp.ones((m,), jnp.int32), 1.0,
        jax.random.PRNGKey(0))
    compiled = "tpu_custom_call" in lowered.as_text()
    _check(compiled, "the pallas round lowered without the Mosaic kernel "
           "(interpret mode?)")
    ref = _experiment(spec).run(seed=0)
    dw = _w_diff(report.result.W, ref.result.W)
    derr = abs(_err(report) - _err(ref))
    _line(phase="kernel", fed=spec.name, ok=dw <= TOL_W and derr <= TOL_ERR,
          tpu_custom_call=compiled, compile_s=compile_s, run_s=run_s,
          gap_last=report.history["gap"][-1], tol_W=TOL_W,
          dW_vs_local=dw, tol_err=TOL_ERR, derr_vs_local=derr)
    _check(dw <= TOL_W, f"W differs from the local engine by {dw} > {TOL_W}")
    _check(derr <= TOL_ERR,
           f"held-out error differs from the local engine by {derr}")


def phase_serve() -> None:
    from repro.api import Eval, Exec, Experiment, Method, Problem, Systems
    from repro.cohort import Population, PopulationSpec
    from repro.core import BudgetConfig, Probabilistic
    from repro.serve.store import resolve_weights
    from repro.utils.timing import tick
    spec = PopulationSpec("chip_smoke_serve", m=100_000, d=32, n_min=16,
                          n_max=64, clusters=5)
    exp = Experiment(
        problem=Problem(population=Population(spec, seed=0)),
        method=Method(loss="hinge",
                      regularizers=Probabilistic(lam=1e-2, sigma2=10.0),
                      rounds=SERVE_BLOCKS, budget=BudgetConfig(passes=1.0)),
        systems=Systems(dropout=0.1),
        exec=Exec(cohort=64, clusters=spec.clusters, degrade=False),
        eval=Eval(record_every=SERVE_BLOCKS))
    sess = exp.serve(seed=0)
    rng = np.random.default_rng(0)

    def predict_and_check() -> float:
        ids = rng.integers(0, spec.m, SERVE_BATCH)
        X = rng.standard_normal((SERVE_BATCH, spec.d)).astype(np.float32)
        before = sess.store.current()
        out = sess.predict(ids, X)
        snap = before if sess.predictor.snapshot_version == before.version \
            else sess.store.current()
        _check(sess.predictor.snapshot_version == snap.version,
               "could not pin the snapshot a predict used")
        W = resolve_weights(snap.centroids, snap.assign, snap.cache_ids,
                            snap.cache_delta, ids)
        ref = np.einsum("bd,bd->b", W.astype(np.float64),
                        X.astype(np.float64))
        return float(np.abs(out - ref).max() / max(1.0, np.abs(ref).max()))

    t0 = tick()
    predict_and_check()                      # compile the lookup
    compile_s = tick() - t0
    t0 = tick()
    sess.start()
    diffs, streaming = [], 0
    # a failed training thread leaves result() None: the deadline bounds the
    # reads, and join() re-raises the failure
    while ((len(diffs) < SERVE_PREDICTS or sess.result() is None)
           and tick() - t0 < SERVE_DEADLINE_S):
        if sess.result() is None:
            streaming += 1
        diffs.append(predict_and_check())
        if len(diffs) >= 10 * SERVE_PREDICTS:
            time.sleep(0.05)                 # training outlives the reads
    result = sess.join()
    train_s = tick() - t0
    diffs.append(predict_and_check())        # the final snapshot
    prov = sess.report().provenance
    worst = max(diffs)
    ok = (worst <= TOL_MARGIN and prov["retries"] == 0
          and prov["degraded_blocks"] == 0 and streaming > 0)
    _line(phase="serve", m=spec.m, ok=ok, compile_s=compile_s,
          run_s=train_s, blocks=SERVE_BLOCKS, predicts=len(diffs),
          predicts_while_training=streaming,
          snapshot_version=sess.snapshot_version,
          gap_last=result.final("gap"), retries=prov["retries"],
          degraded_blocks=prov["degraded_blocks"], tol_margin=TOL_MARGIN,
          dmargin_vs_host=worst)
    _check(worst <= TOL_MARGIN, f"predictions differ from the host rule "
           f"by {worst} > {TOL_MARGIN}")
    _check(prov["retries"] == 0 and prov["degraded_blocks"] == 0,
           f"faults on a clean run: {prov['retries']} retries, "
           f"{prov['degraded_blocks']} degraded blocks")
    _check(streaming > 0, "no predict was answered while blocks streamed")


def phase_sharded(spec) -> None:
    from repro.core.engine import ShardedEngine
    from repro.federated.runtime import make_federated_mesh
    mesh = make_federated_mesh(4)
    devices = {d.id for d in mesh.devices.flat}
    _check(len(devices) == 4, f"mesh holds {len(devices)} distinct devices")
    engine = ShardedEngine(mesh=mesh)
    report, compile_s, run_s = _timed(
        lambda: _experiment(spec, engine=engine).run(seed=0))
    _check(engine.m_pad == 32, f"m padded to {engine.m_pad}, not 32")
    ref = _experiment(spec).run(seed=0)      # local engine, devices()[0]
    dw = _w_diff(report.result.W, ref.result.W)
    derr = abs(_err(report) - _err(ref))
    _line(phase="sharded", fed=spec.name, ok=dw <= TOL_W and derr <= TOL_ERR,
          devices=sorted(devices), m_pad=engine.m_pad, compile_s=compile_s,
          run_s=run_s, gap_last=report.history["gap"][-1], tol_W=TOL_W,
          dW_vs_local=dw, tol_err=TOL_ERR, derr_vs_local=derr)
    _check(dw <= TOL_W, f"W differs from the local engine by {dw} > {TOL_W}")
    _check(derr <= TOL_ERR,
           f"held-out error differs from the local engine by {derr}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine across four chips")
    args = ap.parse_args()

    from repro.data.synthetic import HUMAN_ACTIVITY, VEHICLE_SENSOR
    from repro.utils.jax_compat import use_compile_cache
    use_compile_cache(ROOT)
    try:
        phase_device(args.chips)
    except AssertionError as e:
        _line(phase="device", ok=False, error=repr(str(e)))
        return 1

    if args.chips == 4:
        phases = [lambda: phase_sharded(HUMAN_ACTIVITY)]
    else:
        phases = [lambda: phase_train(HUMAN_ACTIVITY),
                  lambda: phase_train(VEHICLE_SENSOR),
                  lambda: phase_kernel(HUMAN_ACTIVITY),
                  phase_serve]
    failed = 0
    for phase in phases:
        try:
            phase()
        except Exception:  # noqa: BLE001 -- counted, printed, and fails the run
            failed += 1
            traceback.print_exc()
            sys.stdout.flush()
    if failed:
        print(f"{failed} phase(s) failed", file=sys.stderr)
        return 1
    dev = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
