"""FedFusion feature-fusion modules (paper §3.2).

Operators map (E_g(x), E_l(x)) in R^{...xC} x R^{...xC} -> R^{...xC}:
  conv   : W . concat(E_g, E_l) over channels, W in R^{2C x C}
  multi  : lam * E_g + (1 - lam) * E_l, learned per-channel lam in R^C
  single : scalar learned lam

The channel axis is the last axis: C x H x W CNN feature maps are handled
as NHWC, transformer hidden states as [B, S, d] with C = d.

Aggregation: `conv` weights average like any parameter; `multi`/`single`
gates use an exponential moving average (paper §3.3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import dense_init

FUSION_OPS = ("conv", "multi", "single")


def fusion_init(op: str, channels: int, key, dtype=jnp.float32):
    if op == "conv":
        # initialise at "average the two streams": W = 0.5 * [I; I]
        eye = jnp.eye(channels, dtype=dtype)
        w = jnp.concatenate([0.5 * eye, 0.5 * eye], axis=0)
        noise = dense_init(key, (2 * channels, channels), dtype) * 0.01
        return {"w": w + noise}
    if op == "multi":
        return {"lam": jnp.full((channels,), 0.5, dtype)}
    if op == "single":
        return {"lam": jnp.full((), 0.5, dtype)}
    raise ValueError(op)


def fusion_apply(op: str, params, f_g, f_l, *, impl="auto"):
    if op == "conv":
        return ops.fused_fusion_conv(f_g, f_l, params["w"], impl=impl)
    lam = params["lam"]
    return lam * f_g + (1.0 - lam) * f_l


def fusion_aggregate(op: str, old_global, client_fusions, weights, ema_beta,
                     shard=None):
    """Aggregate per-client fusion params returned after local training.

    ``client_fusions``: pytree with a leading client axis.
    ``weights``: [n_clients], sums to 1 (n_t-weighted).
    conv -> weighted average; multi/single -> EMA between the old global
    gate and the weighted client average (paper: EMA smoothing).

    ``shard`` (:class:`repro.core.aggregate.ClientSharding`): inside a
    ``shard_map`` body the client axis holds only this shard's clients;
    the weighted average is completed with one ``psum`` over the client
    mesh axes BEFORE the EMA (the gate statistic is a round-global
    quantity, the EMA must see the full-round average exactly once).
    """
    from repro.core.aggregate import client_weighted_sum, psum_tree
    avg = psum_tree(client_weighted_sum(weights, client_fusions), shard)
    if op == "conv":
        return avg
    return jax.tree.map(
        lambda old, new: ema_beta * old + (1.0 - ema_beta) * new,
        old_global, avg)
