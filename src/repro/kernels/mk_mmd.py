"""Pallas TPU kernel: fused multi-width RBF Gram-sum for MK-MMD.

Computes  S(x, y) = sum_{i<n, j<m} mean_w exp(-||x_i - y_j||^2 / (2 w sigma))
without materialising the n x m Gram matrix in HBM.  Squared distances are
formed per VMEM tile via the ||x||^2 + ||y||^2 - 2 x.y identity, so the
inner product runs on the MXU (at f32 precision, like the oracle: the
identity cancels, and a one-pass bf16 product would swamp small
distances); all RBF widths are applied to the distance tile in-register
and accumulated into one SMEM scalar.  HBM traffic is O((n+m) d),
arithmetic intensity ~ O(tile).

The kernel is the forward pass; :func:`gram_sum` carries a custom VJP whose
backward forms the n x m weight matrix dS/dd2 in plain jnp (Pallas calls
have no reverse-mode rule of their own).

MMD^2 then assembles three of these sums (xx, yy, xy) on the host side of
the kernel (see ops.mk_mmd2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _sq_dists(x, y):
    """Clamped squared distances [n, m] by the inner-product identity."""
    x2 = jnp.sum(x * x, axis=-1)
    y2 = jnp.sum(y * y, axis=-1)
    xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
    return jnp.maximum(x2[:, None] + y2[None, :] - 2.0 * xy, 0.0)


def _gram_sum_kernel(x_ref, y_ref, sigma_ref, out_ref, *, widths, n, m,
                     tile_i, tile_j):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[0, 0] = 0.0

    d2 = _sq_dists(x_ref[...].astype(jnp.float32),
                   y_ref[...].astype(jnp.float32))     # [ti, tj]
    sigma = sigma_ref[0, 0]

    # validity mask for the padded tail rows/cols
    row = i * tile_i + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    col = j * tile_j + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    valid = (row < n) & (col < m)

    acc = jnp.zeros_like(d2)
    for w in widths:
        acc = acc + jnp.exp(-d2 / (2.0 * w * sigma))
    acc = jnp.where(valid, acc, 0.0)
    out_ref[0, 0] += jnp.sum(acc) / len(widths)


def _gram_sum_fwd_call(x, y, sigma, widths, tile_i, tile_j, interpret):
    n, d = x.shape
    m = y.shape[0]
    ti = min(tile_i, max(8, n))
    tj = min(tile_j, max(8, m))
    pn = (-n) % ti
    pm = (-m) % tj
    if pn:
        x = jnp.pad(x, ((0, pn), (0, 0)))
    if pm:
        y = jnp.pad(y, ((0, pm), (0, 0)))
    grid = (x.shape[0] // ti, y.shape[0] // tj)
    sigma = jnp.asarray(sigma, jnp.float32).reshape(1, 1)

    kernel = functools.partial(_gram_sum_kernel, widths=widths, n=n, m=m,
                               tile_i=ti, tile_j=tj)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ti, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tj, d), lambda i, j: (j, 0)),
            smem,
        ],
        out_specs=smem,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        name="gram_sum",
        interpret=interpret,
    )(x, y, sigma)
    return out[0, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gram_sum(x, y, sigma, widths, tile_i, tile_j, interpret):
    return _gram_sum_fwd_call(x, y, sigma, widths, tile_i, tile_j, interpret)


def _gram_sum_fwd(x, y, sigma, widths, tile_i, tile_j, interpret):
    out = _gram_sum_fwd_call(x, y, sigma, widths, tile_i, tile_j, interpret)
    return out, (x, y, sigma)


def _gram_sum_bwd(widths, tile_i, tile_j, interpret, res, g):
    del tile_i, tile_j, interpret
    x, y, sigma = res
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    d2 = _sq_dists(xf, yf)
    # G = dS/dd2 (zero where the clamp at 0 was active); dS/dsigma from
    # the same exponentials
    gd2 = jnp.zeros_like(d2)
    gsig = jnp.zeros_like(d2)
    for w in widths:
        e = jnp.exp(-d2 / (2.0 * w * sigma))
        gd2 = gd2 - e / (2.0 * w * sigma)
        gsig = gsig + e * d2 / (2.0 * w * sigma * sigma)
    gd2 = jnp.where(d2 > 0.0, gd2, 0.0) * (g / len(widths))
    dot = functools.partial(jnp.matmul, precision=_HIGHEST)
    # d2_ij = |x_i|^2 + |y_j|^2 - 2 x_i.y_j
    dx = 2.0 * (jnp.sum(gd2, axis=1)[:, None] * xf - dot(gd2, yf))
    dy = 2.0 * (jnp.sum(gd2, axis=0)[:, None] * yf - dot(gd2.T, xf))
    dsigma = jnp.sum(gsig) * (g / len(widths))
    return (dx.astype(x.dtype), dy.astype(y.dtype),
            jnp.asarray(dsigma, jnp.result_type(sigma)).reshape(
                jnp.shape(sigma)))


_gram_sum.defvjp(_gram_sum_fwd, _gram_sum_bwd)


def gram_sum(x, y, sigma, widths, *, tile_i=TILE, tile_j=TILE,
             interpret=False):
    """sum_{ij} mean_w RBF_w(||x_i - y_j||^2); x [n,d], y [m,d]."""
    return _gram_sum(x, y, sigma, tuple(widths), tile_i, tile_j, interpret)
