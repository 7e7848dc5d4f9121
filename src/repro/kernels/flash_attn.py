"""Pallas TPU kernel: GQA flash attention with a flash BACKWARD pass.

The §Perf analysis (EXPERIMENTS.md, hillclimb 1) showed that the jnp
scan-based flash attention materialises its probability tiles as scan
residuals under autodiff — the S x S score matrix hits HBM in the
backward even under remat, which is the dominant memory term of every
train_4k pair.  The fix is this kernel: forward and backward are
custom-calls whose probability tiles live only in VMEM, so HBM traffic
is O(S·d) (q, k, v, o, do, dq, dk, dv and the [S]-sized softmax stats).

Layout follows kernels/decode_attn.py: grid over (batch, kv_head,
outer block, inner block) with VMEM scratch carrying the online-softmax
state across the innermost grid axis; the rep = H/KV query heads of a
KV group are processed together so each K/V tile is read once per group.

Backward math (standard flash, Dao et al.):
    p_ij = exp(s_ij - lse_i)
    dv_j = sum_i p_ij^T do_i
    dp   = do_i v_j^T
    ds   = p ∘ (dp - D_i),  D_i = rowsum(do_i ∘ o_i)
    dq_i = sum_j ds k_j * scale
    dk_j = sum_i ds^T q_i * scale

Three pallas_calls: forward (o, lse), dq (inner loop over kv blocks),
dkv (inner loop over q blocks).  Causal + sliding-window masks are
applied by position arithmetic inside the tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
Q_BLOCK = 128
KV_BLOCK = 128


def _mask(q_pos, k_pos, *, causal, window, seq_len):
    m = k_pos[None, :] < seq_len
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m  # [qb, kb]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, window, q_block, kv_block, n_kv, seq_len):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0].astype(jnp.float32)        # [qb, rep, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)        # [kb, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)        # [kb, hd]

    q_pos = i * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0)[:, 0]
    k_pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (kv_block, 1), 0)[:, 0]
    mask = _mask(q_pos, k_pos, causal=causal, window=window, seq_len=seq_len)

    # s [rep, qb, kb]
    s = jax.lax.dot_general(q.transpose(1, 0, 2), k,
                            (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None], s, NEG_INF)

    m_prev = m_ref[...]                           # [rep, qb]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0] = (acc_ref[...] / l[..., None]).transpose(1, 0, 2) \
            .astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_fwd(q, k, v, *, scale, causal, window, q_block, kv_block,
              interpret):
    B, S0, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qb = min(q_block, S0)
    kb = min(kv_block, S0)
    q = _pad_to(q, 1, qb)
    k = _pad_to(k, 1, kb)
    v = _pad_to(v, 1, kb)
    Sq, Sk = q.shape[1], k.shape[1]
    nq, nk = Sq // qb, Sk // kb

    qh = q.reshape(B, Sq, KV, rep, hd)   # BlockSpec maps (b, i, g, 0, 0)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        q_block=qb, kv_block=kb, n_kv=nk, seq_len=S0)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, 1, rep, hd), lambda b, g, i, j: (b, i, g, 0, 0)),
            pl.BlockSpec((1, kb, 1, hd), lambda b, g, i, j: (b, j, g, 0)),
            pl.BlockSpec((1, kb, 1, hd), lambda b, g, i, j: (b, j, g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qb, 1, rep, hd), lambda b, g, i, j: (b, i, g, 0, 0)),
            pl.BlockSpec((1, 1, rep, qb), lambda b, g, i, j: (b, g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, KV, rep, hd), q.dtype),
            jax.ShapeDtypeStruct((B, KV, rep, nq * qb), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, qb), jnp.float32),
            pltpu.VMEM((rep, qb), jnp.float32),
            pltpu.VMEM((rep, qb, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qh, k, v)
    o = o.reshape(B, Sq, H, hd)[:, :S0]
    return o, lse  # lse [B, KV, rep, Sq]


# ---------------------------------------------------------------------------
# Backward: dq  (grid inner axis = kv blocks)
# ---------------------------------------------------------------------------

def _dq_kernel_real(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dq_ref,
                    acc_ref, *, scale, causal, window, q_block, kv_block,
                    n_kv, seq_len):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0].astype(jnp.float32).transpose(1, 0, 2)   # [rep, qb, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)                      # [kb, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)                      # [kb, hd]
    do = do_ref[0, :, 0].astype(jnp.float32).transpose(1, 0, 2)
    lse = lse_ref[0, 0]                                         # [rep, qb]
    dcap = dcap_ref[0, 0]                                       # [rep, qb]

    q_pos = i * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0)[:, 0]
    k_pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (kv_block, 1), 0)[:, 0]
    mask = _mask(q_pos, k_pos, causal=causal, window=window, seq_len=seq_len)

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])                             # [rep, qb, kb]
    dp = jax.lax.dot_general(do, v, (((2,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dcap[..., None])                             # [rep, qb, kb]
    acc_ref[...] += jax.lax.dot_general(
        ds, k, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(j == n_kv - 1)
    def _finish():
        dq_ref[0, :, 0] = acc_ref[...].transpose(1, 0, 2).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dk, dv  (grid inner axis = q blocks)
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, window, q_block, kv_block, n_q, seq_len):
    j = pl.program_id(2)   # kv block (outer)
    i = pl.program_id(3)   # q block (inner)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, :, 0].astype(jnp.float32).transpose(1, 0, 2)   # [rep, qb, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)                      # [kb, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)
    do = do_ref[0, :, 0].astype(jnp.float32).transpose(1, 0, 2)
    lse = lse_ref[0, 0]
    dcap = dcap_ref[0, 0]

    q_pos = i * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0)[:, 0]
    k_pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (kv_block, 1), 0)[:, 0]
    mask = _mask(q_pos, k_pos, causal=causal, window=window, seq_len=seq_len)

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])                             # [rep, qb, kb]

    # dv_j += sum_rep p^T do : contract rep+qb
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0, 1), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32)                     # [kb, hd]
    dp = jax.lax.dot_general(do, v, (((2,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dcap[..., None])
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0, 1), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32) * scale             # [kb, hd]

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0, :, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, :, 0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

def make_flash_attention(*, causal=True, window: Optional[int] = None,
                         q_block=Q_BLOCK, kv_block=KV_BLOCK,
                         interpret=False):
    """Returns flash(q, k, v) -> o with a flash (tile-recompute) backward.

    q [B,S,H,hd]; k,v [B,S,KV,hd] with H = KV*rep.  The S x S probability
    matrix never leaves VMEM in either direction.
    """

    @jax.custom_vjp
    def flash(q, k, v):
        o, _ = _fwd(q, k, v)
        return o

    def _fwd(q, k, v):
        hd = q.shape[-1]
        return flash_fwd(q, k, v, scale=hd ** -0.5, causal=causal,
                         window=window, q_block=q_block, kv_block=kv_block,
                         interpret=interpret)

    def fwd_rule(q, k, v):
        o, lse = _fwd(q, k, v)
        return o, (q, k, v, o, lse)

    def bwd_rule(res, do):
        q, k, v, o, lse = res
        B, S0, H, hd = q.shape
        KV = k.shape[2]
        rep = H // KV
        scale = hd ** -0.5
        qb = min(q_block, S0)
        kb = min(kv_block, S0)

        # D_i = rowsum(do * o): O(S*hd), computed outside the kernels
        dcap = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        dcap = dcap.reshape(B, S0, KV, rep).transpose(0, 2, 3, 1)  # [B,KV,rep,S]

        qp = _pad_to(q, 1, qb)
        dop = _pad_to(do, 1, qb)
        kp = _pad_to(k, 1, kb)
        vp = _pad_to(v, 1, kb)
        Sq, Sk = qp.shape[1], kp.shape[1]
        nq, nk = Sq // qb, Sk // kb
        lsep = _pad_to(lse, 3, qb)[..., :Sq]
        dcapp = _pad_to(dcap, 3, qb)[..., :Sq]

        qh = qp.reshape(B, Sq, KV, rep, hd)
        doh = dop.reshape(B, Sq, KV, rep, hd)

        qspec = pl.BlockSpec((1, qb, 1, rep, hd),
                             lambda b, g, i, j: (b, i, g, 0, 0))
        kspec = pl.BlockSpec((1, kb, 1, hd), lambda b, g, i, j: (b, j, g, 0))
        sspec = pl.BlockSpec((1, 1, rep, qb), lambda b, g, i, j: (b, g, 0, i))

        dq_kernel = functools.partial(
            _dq_kernel_real, scale=scale, causal=causal, window=window,
            q_block=qb, kv_block=kb, n_kv=nk, seq_len=S0)
        dq = pl.pallas_call(
            dq_kernel,
            grid=(B, KV, nq, nk),
            in_specs=[qspec, kspec, kspec, qspec, sspec, sspec],
            out_specs=pl.BlockSpec((1, qb, 1, rep, hd),
                                   lambda b, g, i, j: (b, i, g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, Sq, KV, rep, hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((rep, qb, hd), jnp.float32)],
            interpret=interpret,
        )(qh, kp, vp, doh, lsep, dcapp)
        dq = dq.reshape(B, Sq, H, hd)[:, :S0]

        # dk/dv: swap grid so q blocks are innermost
        qspec2 = pl.BlockSpec((1, qb, 1, rep, hd),
                              lambda b, g, j, i: (b, i, g, 0, 0))
        kspec2 = pl.BlockSpec((1, kb, 1, hd), lambda b, g, j, i: (b, j, g, 0))
        sspec2 = pl.BlockSpec((1, 1, rep, qb), lambda b, g, j, i: (b, g, 0, i))
        dkv_kernel = functools.partial(
            _dkv_kernel, scale=scale, causal=causal, window=window,
            q_block=qb, kv_block=kb, n_q=nq, seq_len=S0)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(B, KV, nk, nq),
            in_specs=[qspec2, kspec2, kspec2, qspec2, sspec2, sspec2],
            out_specs=[
                pl.BlockSpec((1, kb, 1, hd), lambda b, g, j, i: (b, j, g, 0)),
                pl.BlockSpec((1, kb, 1, hd), lambda b, g, j, i: (b, j, g, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Sk, KV, hd), k.dtype),
                jax.ShapeDtypeStruct((B, Sk, KV, hd), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((kb, hd), jnp.float32),
                pltpu.VMEM((kb, hd), jnp.float32),
            ],
            interpret=interpret,
        )(qh, kp, vp, doh, lsep, dcapp)
        dk = dk[:, :S0]
        dv = dv[:, :S0]
        return dq, dk, dv

    flash.defvjp(fwd_rule, bwd_rule)
    return flash
