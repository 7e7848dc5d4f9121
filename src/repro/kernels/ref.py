"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; the Pallas kernels must match them (tests sweep
shapes/dtypes and assert_allclose against these).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sq_dists(a, b):
    """Squared distances [n, m] by the ||a||^2 + ||b||^2 - 2 a.b identity.

    The identity cancels, so the inner product runs at f32 precision (a
    one-pass bf16 product, the TPU default, swamps small distances) and
    the rounding noise below zero is clamped away."""
    a2 = jnp.sum(a * a, axis=-1)
    b2 = jnp.sum(b * b, axis=-1)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(a2[:, None] + b2[None, :] - 2.0 * ab, 0.0)


def gram_sum_ref(x, y, sigma, widths):
    """sum_{ij} mean_w exp(-||x_i - y_j||^2 / (2 w sigma)): the Gram sum the
    ``gram_sum`` kernel forms tile by tile, here with the n x m matrix."""
    d2 = sq_dists(x.astype(jnp.float32), y.astype(jnp.float32))
    k = 0.0
    for w in widths:
        k = k + jnp.exp(-d2 / (2.0 * w * sigma))
    return jnp.sum(k) / len(widths)


def mk_mmd2_ref(x, y, widths, *, median_heuristic=True):
    """Multi-kernel (multi-width RBF) squared MMD — paper Eq. (2).

    x [n,d], y [m,d] feature batches.  Biased V-statistic estimator:
        MMD^2 = E[K(x,x)] + E[K(y,y)] - 2 E[K(x,y)]
    with K = mean over RBF kernels exp(-||a-b||^2 / (2 w sigma)).
    ``sigma`` is the (stop-grad) mean pairwise squared distance (median-
    heuristic surrogate) so the widths are scale-free, matching MK-MMD
    practice (Gretton et al. 2012).
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if median_heuristic:
        sigma = jax.lax.stop_gradient(jnp.mean(sq_dists(x, y))) + 1e-8
    else:
        sigma = 1.0

    def kmean(a, b):
        return gram_sum_ref(a, b, sigma, widths) / (a.shape[0] * b.shape[0])

    return kmean(x, x) + kmean(y, y) - 2.0 * kmean(x, y)


def fusion_conv_ref(f_g, f_l, w):
    """1x1-conv fusion operator (paper Eq. 6).

    f_g, f_l [..., C]; w [2C, C].  Equivalent to concat along the channel
    axis followed by a 1x1 convolution (= matmul over channels).
    """
    C = f_g.shape[-1]
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    return dot(f_g, w[:C]) + dot(f_l, w[C:])


def inv_scale(scale):
    """The f32 reciprocal of a quantizer's step, computed once per call."""
    return jnp.float32(1.0) / jnp.asarray(scale, jnp.float32)


def quant_pack_ref(x, scale, noise, *, bits):
    """Fused stochastic-quantize + pack oracle (repro.compress wire format).

    x [n] float; scale scalar (wire step size); noise [n] in [0,1) — the
    stochastic-rounding offsets (0.5 = deterministic round-half-up).
    ``bits=8``: int8 codes in [-127, 127].
    ``bits=4``: codes in [-7, 7] stored as ``code+8`` nibbles, two per uint8
    (element 2i in the low nibble, 2i+1 in the high one); n must be even.
    ``x / scale`` is taken as ``x * inv_scale(scale)``: a product rounds
    the same in every program, while a TPU division is an expansion that
    differs between XLA and a Pallas kernel in the last bit, enough to
    move a code across a rounding boundary.
    """
    if bits not in (4, 8):
        raise ValueError(f"quant_pack_ref bits={bits!r} must be 4 or 8")
    qmax = 127 if bits == 8 else 7
    q = jnp.floor(x.astype(jnp.float32) * inv_scale(scale) + noise)
    q = jnp.clip(q, -qmax, qmax)
    if bits == 8:
        return q.astype(jnp.int8)
    u = (q + 8).astype(jnp.uint8).reshape(-1, 2)
    return (u[:, 0] | (u[:, 1] << 4)).astype(jnp.uint8)


def quant_unpack_ref(packed, scale, *, bits, n):
    """Inverse of :func:`quant_pack_ref`: packed codes -> float32 [n]."""
    if bits not in (4, 8):
        raise ValueError(f"quant_unpack_ref bits={bits!r} must be 4 "
                         "or 8")
    if bits == 8:
        return packed.astype(jnp.float32) * scale
    low = (packed & 0xF).astype(jnp.int32) - 8
    high = ((packed >> 4) & 0xF).astype(jnp.int32) - 8
    q = jnp.stack([low, high], axis=-1).reshape(-1)[:n]
    return q.astype(jnp.float32) * scale


def topk_select_ref(x, thresh):
    """Magnitude threshold select: keep x where |x| >= thresh, else 0.

    With thresh = the k-th largest |x| this is the dense form of top-k
    sparsification (the decode∘encode of the topk codec)."""
    return jnp.where(jnp.abs(x) >= thresh, x, jnp.zeros_like(x))


def ef_gather_ref(table, idx):
    """Row gather of the device-resident error-feedback table.

    table [N, ...] (one row per federation client), idx [k] int32 — the
    round's sampled client ids.  Returns the [k, ...] rows the round fn
    threads as per-client EF state.
    """
    return jnp.take(table, idx, axis=0)


def ef_scatter_ref(table, idx, rows):
    """Row scatter: write rows [k, ...] back into table [N, ...] at idx.

    ``idx`` must be unique (``FederatedDataset.sample_clients`` asserts
    it); with duplicates ``.at[].set`` keeps the last write, silently
    dropping the other client's residual — the exact hazard the sampler
    guard exists for.
    """
    return table.at[idx].set(rows)


def decode_attn_ref(q, k_cache, v_cache, valid_len):
    """GQA flash-decode oracle.

    q [B,1,H,hd]; caches [B,L,KV,hd]; valid_len scalar int (positions
    >= valid_len are masked).  Returns [B,1,H,hd].
    """
    B, _, H, hd = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    qh = q.reshape(B, KV, rep, hd)
    s = jnp.einsum("bgrd,blgd->bgrl", qh.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * hd ** -0.5
    mask = jnp.arange(L) < valid_len
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrl,blgd->bgrd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)
