"""Small shims over JAX: pinned rounding, float32 matmuls, the compile cache.

``fp_barrier``: a vmap-safe scalar/array identity that pins floating-point
rounding at op boundaries.  XLA's CPU backend contracts a product feeding
an add into an FMA whenever both land in one fusion, and it decides per
fusion context, so the same formula compiled inside a vmapped solver and
inside a Pallas (interpret) kernel can differ by 1 ulp per step.  The SDCA
engines pin every product-into-add so all round engines are bit-identical
on the CPU (tests/test_runtime.py).  ``lax.optimization_barrier`` alone no
longer does this on jax 0.9: XLA removes it before fusion.  On the CPU the
pin is therefore a NaN-preserving select, which LLVM cannot contract
through; other platforms get the barrier only (``lax.platform_dependent``),
so the chip pays nothing for a CPU contract.  Mosaic (the Pallas TPU
compiler) lowers neither, so the compiled SDCA kernel uses unpinned twins
of these primitives (DESIGN.md §3).

``F32_DOT``: the precision every float32 matmul of the MOCHA path asks for.
A TPU's default precision rounds float32 matmul operands to bfloat16; the
reference semantics are float32 (the CPU ignores the setting).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

F32_DOT = jax.lax.Precision.HIGHEST


def _cpu_round(x: jax.Array) -> jax.Array:
    return jnp.where(x == x, x, jnp.nan)


def fp_barrier(x: jax.Array) -> jax.Array:
    """Identity that forces ``x`` to round before downstream fusion."""
    x = jax.lax.optimization_barrier(x)
    return jax.lax.platform_dependent(x, cpu=_cpu_round, default=lambda y: y)


def use_compile_cache(root: str) -> None:
    """Keep JAX's persistent compile cache at one fixed place.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``<root>/.jax_cache``.  The
    path is part of the cache key, so it must not move between runs."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(os.path.abspath(root), ".jax_cache"))
