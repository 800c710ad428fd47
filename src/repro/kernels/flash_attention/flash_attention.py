"""Flash attention Pallas TPU kernel: block-tiled online-softmax causal
attention with optional sliding window.

Tiling (per DESIGN.md hardware adaptation): the grid iterates
(batch*heads, q_blocks); each kernel instance holds one (BLOCK_Q, head_dim)
query tile in VMEM and streams (BLOCK_K, head_dim) key/value tiles through a
fori_loop, maintaining the online-softmax running max / normalizer / output
accumulator in f32.  Block sizes default to 128 (MXU-aligned: the q x k tile
matmul is 128x128) and the working set is
(BLOCK_Q + 2*BLOCK_K) * head_dim * 4B + BLOCK_Q*BLOCK_K*4B -- well under the
~16 MiB v5e VMEM for head_dim <= 256.

Validated against kernels/flash_attention/ref.py in interpret mode on CPU
(this container); on real TPUs drop ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, seq_len: int,
                  causal: bool, window: int | None, scale: float):
    """One (q_block, head) tile. Shapes in refs:
    q_ref: (block_q, d); k_ref/v_ref: (seq_len, d); o_ref: (block_q, d)."""
    block_q, d = q_ref.shape
    q_idx = pl.program_id(1)
    q0 = q_idx * block_q

    q = q_ref[...].astype(jnp.float32) * scale
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    n_k = seq_len // block_k

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k0 = kb * block_k
        k = k_ref[pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(k0, block_k), :].astype(jnp.float32)
        s = q @ k.T                                     # (bq, bk) f32
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    if causal:
        # skip key blocks strictly after this query block
        n_live = jnp.minimum(n_k, (q0 + block_q + block_k - 1) // block_k)
    else:
        n_live = n_k
    acc, m, l = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q/k/v: (B, H, S, D) (kv heads already broadcast). Returns (B,H,S,D)."""
    b, h, s, d = q.shape
    assert k.shape == v.shape == (b, h, s, d), (q.shape, k.shape)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    scale = 1.0 / d ** 0.5

    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)

    kernel = functools.partial(_flash_kernel, block_k=block_k, seq_len=s,
                               causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, s, d), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, s, d), lambda bh, qb: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s, d)
