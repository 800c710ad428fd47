"""Single-token decode attention Pallas kernel (flash-decode style).

The decode_32k / long_500k serving shapes are dominated by streaming a long
KV cache past one query token.  Grid: (batch*heads,); each instance streams
(BLOCK_K, d) cache tiles through VMEM with an online-softmax accumulator.
On the production mesh the cache's sequence axis is sharded over ``model``;
each shard runs this kernel on its slice and the partial (m, l, acc) stats
merge with a tiny all-reduce -- the kernel computes per-slice results that
are exact for its tile range.

Validated against ref.py in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, *, block_k: int,
                   seq_len: int, scale: float):
    """q: (d,); k/v: (seq_len, d); len: (1,) valid cache length; o: (d,)."""
    d = q_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale
    valid = len_ref[0]
    n_k = seq_len // block_k

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k0 = kb * block_k
        k = k_ref[pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(k0, block_k), :].astype(jnp.float32)
        s = k @ q                                      # (block_k,)
        pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
        s = jnp.where(pos < valid, s, NEG_INF)
        m_cur = jnp.max(s)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p)
        acc = acc * alpha + p @ v
        return acc, m_new, l_new

    acc0 = jnp.zeros((d,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(
        0, n_k, body, (acc0, jnp.float32(NEG_INF), jnp.float32(0.0)))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, block_k: int = 512,
                     interpret: bool = True):
    """q: (B, H, D); k/v: (B, H, T, D); lengths: (B,) valid cache lengths."""
    b, h, d = q.shape
    t = k.shape[2]
    block_k = min(block_k, t)
    assert t % block_k == 0, (t, block_k)
    scale = 1.0 / d ** 0.5
    qr = q.reshape(b * h, d)
    kr = k.reshape(b * h, t, d)
    vr = v.reshape(b * h, t, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), h).reshape(b * h, 1)

    kernel = functools.partial(_decode_kernel, block_k=block_k, seq_len=t,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h,),
        in_specs=[
            pl.BlockSpec((None, d), lambda i: (i, 0)),
            pl.BlockSpec((None, t, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, t, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr, lens)
    return out.reshape(b, h, d)
