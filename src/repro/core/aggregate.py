"""Server-side aggregation (paper Alg. 1 / Alg. 2 line 7).

All aggregations take an optional ``shard`` — a :class:`ClientSharding`
describing how the round's client axis is split over mesh axes inside a
``shard_map`` body.  With ``shard=None`` (the default, and the only mode
exercised on a single device) every function is exactly the pre-sharding
code path: a pure in-shard reduction with no collectives, so single-device
results stay bitwise-identical.  With a shard, each function reduces its
local clients in-shard and finishes with one ``psum`` over the client mesh
axes — the only cross-device communication FedAvg actually requires.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import AGGREGATE, in_part


@dataclass(frozen=True)
class ClientSharding:
    """How the round's client axis maps onto mesh axes (``shard_map`` body).

    ``axes``/``sizes``: the mesh axis names the client dimension is split
    over (in major-to-minor order, e.g. ``("pod", "data")``) and their
    static sizes.  Instances only make sense inside a ``shard_map`` over
    those axes; the factories in ``repro.core.rounds`` treat ``None`` as
    "unsharded".
    """

    axes: Tuple[str, ...]
    sizes: Tuple[int, ...]

    @property
    def axis_name(self):
        """The axis-name argument collectives take (str or tuple)."""
        return self.axes if len(self.axes) > 1 else self.axes[0]

    @property
    def n_shards(self) -> int:
        n = 1
        for s in self.sizes:
            n *= s
        return n

    def position(self):
        """This shard's row-major position along the client axis (traced)."""
        pos = jnp.zeros((), jnp.int32)
        for a, s in zip(self.axes, self.sizes):
            pos = pos * s + jax.lax.axis_index(a)
        return pos


@in_part(AGGREGATE)
def psum_tree(tree, shard: ClientSharding):
    """``psum`` every leaf over the client axes (identity when unsharded)."""
    if shard is None:
        return tree
    return jax.lax.psum(tree, shard.axis_name)


def fused_psum(tree, shard: ClientSharding):
    """Sum every leaf over the client axes in ONE collective.

    Ravels and concatenates all leaves into a single flat buffer, runs one
    ``psum`` over it, and unpacks via static slices — pack offsets are pure
    trace-time Python (leaf shapes are static), so the whole exchange
    lowers to a single all-reduce regardless of how many quantities ride
    it.  ``psum`` reduces elementwise in a participant order fixed by the
    mesh, so every unpacked leaf is bitwise what a standalone ``psum`` of
    that leaf would have produced — packing is a latency optimization,
    never a numerics change.  Identity when unsharded.

    All leaves must share one dtype (the engine's fused round buckets are
    float32 end to end); mixed-dtype trees raise instead of silently
    promoting through the concatenation.
    """
    if shard is None:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    mixed = {str(l.dtype) for l in leaves}
    if len(mixed) > 1:
        raise TypeError(
            f"fused_psum needs a single-dtype tree, got {sorted(mixed)}; "
            f"run the unfused collectives (fused_collective=False) for "
            f"mixed-precision buckets")
    flat = (jnp.concatenate([jnp.ravel(l) for l in leaves])
            if len(leaves) > 1 else jnp.ravel(leaves[0]))
    summed = jax.lax.psum(flat, shard.axis_name)
    out, off = [], 0
    for l in leaves:
        out.append(jax.lax.slice_in_dim(summed, off, off + l.size)
                   .reshape(l.shape))
        off += l.size
    return jax.tree_util.tree_unflatten(treedef, out)


@in_part(AGGREGATE)
def normalize_weights(n_examples, shard: ClientSharding = None):
    n = jnp.asarray(n_examples, jnp.float32)
    total = jnp.sum(n)
    if shard is not None:
        total = jax.lax.psum(total, shard.axis_name)
    return n / total


@in_part(AGGREGATE)
def weights_over_total(n_examples, total):
    """The round's example weights against a psum-completed ``total``
    (the fused rounds pipeline it one collective ahead): the same
    quotient as :func:`normalize_weights`, bit for bit."""
    return jnp.asarray(n_examples, jnp.float32) / total


@in_part(AGGREGATE)
def client_weighted_sum(weights, stacked_tree):
    """``sum_c weights[c] * x[c]`` over the leading client axis of every
    leaf, added in client order from zero by a loop over the clients.

    A ``tensordot`` over the client axis lowers to a ``reduce`` whose
    association follows the layout XLA gives its operand, and that layout
    follows the operand's producer: on a TPU the same round summed a
    Pallas kernel's decoded deltas and the fused jnp oracle's in different
    orders, and a few percent of the conv weights moved by an ulp.  The
    loop pins the order on every backend (it is the order
    :func:`running_update` accumulates in); unrolled, the same chain of
    adds let XLA fuse each client's slice into one consumer and multiplied
    the round's scratch memory by more than four for the int4 codec."""
    leaves = jax.tree.leaves(stacked_tree)
    if not leaves:
        return stacked_tree

    def add(c, acc):
        return jax.tree.map(
            lambda a, x: a + weights[c].astype(x.dtype)
            * jax.lax.dynamic_index_in_dim(x, c, keepdims=False),
            acc, stacked_tree)

    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype),
                         stacked_tree)
    return jax.lax.fori_loop(0, leaves[0].shape[0], add, zeros)


@in_part(AGGREGATE)
def weighted_mean(stacked_tree, weights, shard: ClientSharding = None):
    """stacked_tree: pytree with leading client axis; weights [n_clients].

    Sharded: :func:`client_weighted_sum` adds this shard's clients, the
    trailing ``psum`` completes the sum over the full round (weights are
    globally normalized by :func:`normalize_weights`).
    """
    return psum_tree(client_weighted_sum(weights, stacked_tree), shard)


@in_part(AGGREGATE)
def mean_over_clients(values, shard: ClientSharding = None):
    """Mean of a per-client [C_local] array over the FULL round's clients."""
    m = jnp.mean(values)
    if shard is None:
        return m
    return jax.lax.pmean(m, shard.axis_name)


@in_part(AGGREGATE)
def masked_loss_sums(losses, pmask):
    """Psum-pending numerator/denominator of a participation-masked mean
    loss.  Rides whatever collective the caller already makes (the fused
    one-psum contribs or the unfused ``psum_tree`` pack) — masking adds
    no collectives of its own."""
    m = pmask.astype(losses.dtype)
    return {"lsum": jnp.sum(losses * m), "lw": jnp.sum(m)}


@in_part(AGGREGATE)
def finish_masked_loss(summed):
    """Post-psum completion of :func:`masked_loss_sums` (the staleness /
    participation finish step: division happens once, after the sum over
    every shard's surviving clients)."""
    return summed["lsum"] / jnp.maximum(summed["lw"], 1.0)


@in_part(AGGREGATE)
def running_update(acc_tree, tree, weight):
    """acc += weight * tree   (client_sequential accumulation)."""
    return jax.tree.map(lambda a, x: a + weight.astype(x.dtype) * x,
                        acc_tree, tree)


@in_part(AGGREGATE)
def zeros_like_tree(tree):
    return jax.tree.map(jnp.zeros_like, tree)
