"""Batched, jit-compiled prediction lookups over a ``SnapshotStore``.

The host path (``ServedSnapshot.client_weights``) exists for parity and
evaluation; this module is the serving fast path.  A ``Predictor`` pins
the current snapshot's arrays on device and answers ``predict(ids, X)``
with one fused gather + searchsorted + dot kernel.  Because snapshots
carry fixed-capacity (cache) and fixed-population (assign) shapes, the
kernel compiles once per population and is reused across every snapshot
version -- a swap costs four device puts, not a recompile.

Serve-role code under the thread-ownership contract: the per-snapshot
device mirror is ``# owner: serve`` and all entry points run on the serve
thread.  The stale-read counter feeds the ``serve_stale_reads`` /
``serve_reads`` metrics pair (stale-read fraction = a read whose snapshot
was superseded while the answer was being computed -- legal, bounded by
one swap, and worth watching).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serve.store import SnapshotStore
from repro.utils.jax_compat import F32_DOT


@jax.jit
def _lookup(assign, centroids, cache_ids, cache_delta, ids):
    """(B, d) served weights on device -- jit twin of store.resolve_weights."""
    W = centroids[assign[ids]]
    capacity = cache_ids.shape[0]
    if capacity:  # static: snapshots pad the cache to a fixed capacity
        pos = jnp.clip(jnp.searchsorted(cache_ids, ids), 0, capacity - 1)
        hit = cache_ids[pos] == ids
        W = W + jnp.where(hit[:, None], cache_delta[pos], jnp.float32(0))
    return W


@jax.jit
def _margins(assign, centroids, cache_ids, cache_delta, ids, X):
    W = _lookup(assign, centroids, cache_ids, cache_delta, ids)
    return jnp.einsum("bd,bd->b", W, X.astype(jnp.float32),
                      precision=F32_DOT)


class Predictor:
    """Answers batched predictions against the store's newest snapshot.

    Single-reader object: one ``Predictor`` per serve thread (the device
    mirror below is serve-owned state, same single-writer discipline as
    the tracer's per-worker buffers).  Multiple serve threads each get
    their own ``Predictor`` over the shared ``SnapshotStore``.
    """

    def __init__(self, store: SnapshotStore,
                 telemetry: Optional[obs.Telemetry] = None):
        # launch-time constants
        self._store = store
        tel = telemetry if telemetry is not None else obs.NULL_TELEMETRY
        self.tel = tel.for_worker("serve")
        self._reads = self.tel.counter("serve_reads")
        self._stale = self.tel.counter("serve_stale_reads")
        self._version: int = -1        # owner: serve
        self._device: Optional[Tuple] = None  # owner: serve
        self._max_lag: int = 0         # owner: serve

    def _arrays(self, snap):  # worker: serve
        """Device mirror of ``snap``, refreshed only on version change."""
        if self._device is None or self._version != snap.version:
            self._device = (jnp.asarray(snap.assign),
                            jnp.asarray(snap.centroids),
                            jnp.asarray(snap.cache_ids),
                            jnp.asarray(snap.cache_delta))
            self._version = snap.version
        return self._device

    def _finish(self, snap, out):  # worker: serve
        host = np.asarray(out)  # blocks until the lookup is done
        self._reads.inc()
        lag = self._store.version - snap.version
        if lag > 0:
            self._stale.inc()  # answered from a just-superseded snapshot
        if lag > self._max_lag:
            self._max_lag = lag
        return host

    def _ids(self, snap, ids):  # worker: serve
        ids = np.asarray(ids, np.int64)
        if ids.size and (ids.min() < 0 or ids.max() >= snap.m):
            raise ValueError(
                f"client ids must be in [0, {snap.m}); got range "
                f"[{ids.min()}, {ids.max()}]")
        return jnp.asarray(ids, jnp.int32)

    def lookup(self, ids) -> np.ndarray:  # worker: serve
        """(B, d) served weights for ``ids`` under the newest snapshot."""
        snap = self._store.current()
        out = _lookup(*self._arrays(snap), self._ids(snap, ids))
        return self._finish(snap, out)

    def predict(self, ids, X) -> np.ndarray:  # worker: serve
        """(B,) decision margins ``<w_id, x>`` for per-client features X."""
        snap = self._store.current()
        X = jnp.asarray(np.asarray(X, np.float32))
        out = _margins(*self._arrays(snap), self._ids(snap, ids), X)
        return self._finish(snap, out)

    @property
    def snapshot_version(self) -> int:
        """Version of the snapshot currently mirrored on device."""
        return self._version

    @property
    def max_version_lag(self) -> int:
        """Worst finish-time staleness any answered read has seen, in
        snapshot swaps (how many publishes completed while the answer was
        being computed).  Reads never stall, so this is a freshness stat,
        not a blocking one; for a warmed predictor whose lookups are much
        shorter than the publish interval it stays ``<= 1`` -- the serving
        bench gates exactly that."""
        return self._max_lag
