"""Pallas TPU kernel: GQA flash-decode (one query token vs a long KV cache).

Decode attention is memory-bound: the whole KV cache streams once through
VMEM per step.  The kernel blocks the cache length, keeps the online-softmax
running (m, l, acc) state in VMEM scratch, and writes the normalised output
on the last cache block.  Grid = (batch, kv_head, cache_blocks); the
rep = H/KV query heads of a KV group are processed together so each K/V tile
is read exactly once (the GQA bandwidth win).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_L = 512


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_l, n_blocks, scale):
    b = pl.program_id(0)   # noqa: F841  (batch handled by BlockSpec)
    g = pl.program_id(1)   # noqa: F841
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)         # [rep, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)         # [Lb, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)         # [Lb, hd]
    valid_len = valid_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = j * block_l + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < valid_len, s, -1e30)

    m_prev = m_ref[...]                            # [rep]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, valid_len=None, *, block_l=BLOCK_L,
                 interpret=False):
    """q [B,1,H,hd]; caches [B,L,KV,hd] -> [B,1,H,hd]."""
    B, _, H, hd = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    bl = min(block_l, L)
    pad = (-L) % bl
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    n_blocks = Lp // bl
    if valid_len is None:
        valid_len = L
    valid = jnp.asarray(valid_len, jnp.int32).reshape(1)

    qh = q.reshape(B, 1, KV, rep, hd).transpose(0, 2, 1, 3, 4)  # [B,KV,1,rep,hd]
    kernel = functools.partial(_decode_kernel, block_l=bl, n_blocks=n_blocks,
                               scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, 1, rep, hd), lambda b, g, j: (b, g, 0, 0, 0)),
            pl.BlockSpec((1, bl, 1, hd), lambda b, g, j: (b, j, g, 0)),
            pl.BlockSpec((1, bl, 1, hd), lambda b, g, j: (b, j, g, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda b, g, j: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep,), jnp.float32),
            pltpu.VMEM((rep, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qh, k_cache, v_cache, valid)
    return out.reshape(B, 1, H, hd)
