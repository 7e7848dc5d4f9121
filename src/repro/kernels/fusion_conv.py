"""Pallas TPU kernel: fused FedFusion `conv` operator.

F_conv(E_g, E_l) = W . concat(E_g, E_l)  with W in R^{2C x C} (paper Eq. 6).
The concat is never materialised: W is consumed as two C x C halves and the
kernel computes  out = E_g @ W_g + E_l @ W_l  tile-by-tile in VMEM, with both
matmuls hitting the MXU and a single accumulator.

Token axis (B*S or B*H*W) is tiled by ``tile_t``; the channel contraction is
done in full per tile (C <= ~8k fits VMEM comfortably at f32).  Both
products run at f32 precision, as the oracle's do.

The kernel is the forward pass; :func:`fusion_conv` carries a custom VJP
whose backward is the four transposed products in plain jnp (Pallas calls
have no reverse-mode rule of their own).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_T = 256


_HIGHEST = jax.lax.Precision.HIGHEST


def _fusion_kernel(fg_ref, fl_ref, wg_ref, wl_ref, out_ref):
    dot = functools.partial(jax.lax.dot, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    acc = dot(fg_ref[...], wg_ref[...]) + dot(fl_ref[...], wl_ref[...])
    out_ref[...] = acc.astype(out_ref.dtype)


def _fusion_call(f_g, f_l, w, tile_t, interpret):
    orig_shape = f_g.shape
    C = orig_shape[-1]
    fg = f_g.reshape(-1, C)
    fl = f_l.reshape(-1, C)
    T = fg.shape[0]
    tt = min(tile_t, T)
    pad = (-T) % tt
    if pad:
        fg = jnp.pad(fg, ((0, pad), (0, 0)))
        fl = jnp.pad(fl, ((0, pad), (0, 0)))
    grid = (fg.shape[0] // tt,)
    wg, wl = w[:C], w[C:]

    out = pl.pallas_call(
        _fusion_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tt, C), lambda i: (i, 0)),
            pl.BlockSpec((tt, C), lambda i: (i, 0)),
            pl.BlockSpec((C, C), lambda i: (0, 0)),
            pl.BlockSpec((C, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tt, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((fg.shape[0], C), f_g.dtype),
        name="fusion_conv",
        interpret=interpret,
    )(fg, fl, wg, wl)
    return out[:T].reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fusion_conv(f_g, f_l, w, tile_t, interpret):
    return _fusion_call(f_g, f_l, w, tile_t, interpret)


def _fusion_conv_fwd(f_g, f_l, w, tile_t, interpret):
    return _fusion_call(f_g, f_l, w, tile_t, interpret), (f_g, f_l, w)


def _fusion_conv_bwd(tile_t, interpret, res, g):
    del tile_t, interpret
    f_g, f_l, w = res
    C = f_g.shape[-1]
    dot = functools.partial(jnp.matmul, precision=_HIGHEST)
    g2 = g.reshape(-1, C)
    dw = jnp.concatenate([dot(f_g.reshape(-1, C).T, g2),
                          dot(f_l.reshape(-1, C).T, g2)], axis=0)
    return (dot(g, w[:C].T).astype(f_g.dtype),
            dot(g, w[C:].T).astype(f_l.dtype), dw.astype(w.dtype))


_fusion_conv.defvjp(_fusion_conv_fwd, _fusion_conv_bwd)


def fusion_conv(f_g, f_l, w, *, tile_t=TILE_T, interpret=False):
    """f_g, f_l [..., C]; w [2C, C] -> fused [..., C]."""
    return _fusion_conv(f_g, f_l, w, tile_t, interpret)
