"""Pack a sampled cohort into the padded ``FederatedData`` layout.

The whole point of the cohort subsystem is that everything below the
sampler is UNCHANGED: a packed cohort is a perfectly ordinary m=K
federation, so ``run_mocha`` and all three round engines (local vmap /
pallas kernel / shard_map) execute it as-is.  Sharding consequently
distributes the K-task cohort over the mesh -- never the population
(``federated.sharding.pad_tasks`` pads the cohort's task axis to the shard
count exactly as for a static federation).

Layout invariants preserved here:

  * left-packed point axis with a fixed width (``PopulationSpec.pad_width``
    by default), so every block of a run compiles to one program shape;
  * ``xnorm2`` threaded: the per-run hoisted row-norm table is filled at
    pack time through ``dual.with_xnorm2`` (the same pinned ``row_norms``
    every engine reads), so a cohort block gets the identical solver
    precompute a static federation gets.

Clients are drawn concurrently: ``client_block(t)`` is a pure function of
``(seed, t)`` with its own counter-based generator, so the cohort's clients
share no state, and numpy's bulk draws and elementwise passes release the
GIL.  Each draw task writes its client straight into its own slot, so the
packed bytes are the same at every pool size.
"""
from __future__ import annotations

import os
import weakref
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cohort.population import Population
from repro.core.dual import FederatedData, with_xnorm2


def _usable_cores() -> int:
    """CPU cores this process may run on (its affinity mask, where known)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:       # no affinity API on this platform
        return os.cpu_count() or 1


class CohortPacker:
    """Reusable cohort packer: layout resolved once, buffers preallocated.

    ``pack_cohort`` re-derives the (K, n_pad, d) layout and allocates three
    fresh staging arrays every block even though cohort shapes are static
    per run.  The packer hoists that per-block host work: the layout
    metadata is resolved once at construction and the staging buffers are
    reused across blocks.  Reuse is safe because ``jnp.array`` COPIES host
    memory onto the device inside ``pack`` -- by the time ``pack`` returns,
    the buffers are free to overwrite (this is why the copying ``jnp.array``
    is used rather than ``jnp.asarray``, which may alias).

    ``pack`` also returns the cohort's true sizes as each slot's draw
    reports them, rather than by summing the packed mask -- the driver's
    per-block ``np.asarray(n_t)`` device pull becomes a pure host
    derivation.

    The clients of a block are drawn on a pool of ``threads`` host
    threads, ``min(cohort, usable cores)``; at 1 the draws run serially in
    the calling thread.  Each task owns one slot: it writes the client's
    rows and zeroes only that slot's padded tail, so no task touches
    another's bytes.  ``close`` (or collecting the packer) releases the
    pool.

    NOT thread-safe across concurrent ``pack`` calls (one packer per
    pipeline stage; the overlapped driver packs on a single worker, and the
    draw pool runs inside that worker's ``pack``) -- the staging buffers
    are ``# owner: pack`` and ``tools/reprolint`` (T301/T302) rejects any
    access from outside pack-tagged functions.

    ``pack`` IS retry-idempotent: every slot is fully overwritten on each
    call, a failed call returns only after all of its draws have finished,
    and no cross-call state accumulates, so the resilience layer
    (repro.cohort.resilience) may re-invoke it for the same block after an
    injected or real pack failure and get a bit-identical federation.
    """

    def __init__(self, pop: Population, cohort: int,
                 n_pad: Optional[int] = None):
        self.pop = pop
        self.n_pad = int(n_pad or pop.spec.pad_width)
        self.cohort = int(cohort)
        d = pop.spec.d
        self._X = np.zeros((self.cohort, self.n_pad, d), np.float32)  # owner: pack
        self._y = np.zeros((self.cohort, self.n_pad), np.float32)  # owner: pack
        self._mask = np.zeros((self.cohort, self.n_pad), np.float32)  # owner: pack
        self.threads = max(1, min(self.cohort, _usable_cores()))
        self._pool: Optional[ThreadPoolExecutor] = None
        self._release = None
        if self.threads > 1:
            self._pool = ThreadPoolExecutor(self.threads, "cohort-draw")
            self._release = weakref.finalize(self, self._pool.shutdown)

    def close(self) -> None:
        """Release the draw pool and wait for its threads (idempotent)."""
        if self._release is not None:
            self._release()

    def _fill(self, slot: int, t: int) -> int:  # worker: pack
        """Draw client ``t`` into slot ``slot``; return its size."""
        block = self.pop.client_block(t)
        if block.n > self.n_pad:
            raise ValueError(
                f"client {t} has n_t={block.n} > n_pad={self.n_pad}; raise "
                "PopulationSpec.n_pad (cohort shapes are static per run)")
        n = block.n
        self._X[slot, :n] = block.X
        self._X[slot, n:] = 0.0
        self._y[slot, :n] = block.y
        self._y[slot, n:] = 0.0
        self._mask[slot, :n] = 1.0
        self._mask[slot, n:] = 0.0
        return n

    def pack(self, ids: Sequence[int]) -> Tuple[FederatedData, np.ndarray]:  # worker: pack
        """(m=K federation, (K,) int64 true sizes) for cohort ``ids``."""
        if len(ids) != self.cohort:
            raise ValueError(
                f"cohort of {len(ids)} clients in a {self.cohort}-slot "
                "packer (cohort shapes are static per run)")
        if self._pool is None:
            sizes = np.asarray([self._fill(slot, int(t))
                                for slot, t in enumerate(ids)], np.int64)
        else:
            draws = [self._pool.submit(self._fill, slot, int(t))
                     for slot, t in enumerate(ids)]
            # every draw finishes before pack returns or raises, so a retry
            # never races a straggler of the failed call; the first failing
            # slot's exception surfaces, as on the serial path
            wait(draws)
            sizes = np.asarray([f.result() for f in draws], np.int64)
        data = with_xnorm2(FederatedData(
            X=jnp.array(self._X), y=jnp.array(self._y),
            mask=jnp.array(self._mask)))
        # the copies above dispatch ASYNCHRONOUSLY: block until the device
        # buffers are materialized, else the next pack's buffer overwrite
        # races the pending copy (jnp.array guarantees a copy, not when)
        jax.block_until_ready(data)
        return data, sizes


def pack_cohort(pop: Population, ids: Sequence[int],
                n_pad: Optional[int] = None) -> FederatedData:
    """Materialize clients ``ids`` and pack them as an m=K federation.

    Memory is O(K * n_pad * d) -- the cohort, never the population.  Slot
    order follows ``ids`` (the schedule's order), so packing is
    deterministic given a schedule.  One-shot convenience over
    ``CohortPacker`` (the block loop reuses a packer instead).
    """
    packer = CohortPacker(pop, len(ids), n_pad)
    try:
        return packer.pack(ids)[0]
    finally:
        packer.close()
