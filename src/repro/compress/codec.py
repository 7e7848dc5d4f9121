"""The ``Codec`` protocol: pluggable uplink/downlink pytree compression.

A codec maps a model pytree (weights or weight deltas) to a *wire payload*
— a pytree whose array leaves are exactly the bytes that would cross the
network — and back.  ``nbytes`` reports true wire size from the payload's
static shapes/dtypes (it also works on ``jax.eval_shape`` results, which is
how the server accounts bytes without running an encode).

Codecs are jax-traceable: ``encode``/``decode`` run under jit/vmap inside
the round function, so per-client compression vectorises with the same
``client_parallel`` vmap that parallelises local training.

Stateful codecs (error feedback) thread a per-client ``state`` pytree
through ``encode``; the federated server persists one state per client
across rounds (see ``repro.fl.server``).

The round consumes ``roundtrip`` (encode, then decode, as the receiver
sees it); ``encode`` alone defines the wire format and its bytes.

Wire-format note: payload leaves are the transmitted buffers; seed-expanded
codecs (sketching) additionally transmit one int32 seed per leaf, carried
in the payload as an array so ``nbytes`` counts it.
"""
from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _nbytes_of(x) -> int:
    """Wire bytes of one payload array (works on ShapeDtypeStruct too)."""
    return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize


class Codec:
    """Base codec: bind to a template tree, then encode/decode leaves.

    Subclasses implement the per-leaf hooks ``_encode_leaf(x_flat, state,
    key, i)`` -> (leaf_payload, new_leaf_state) and ``_decode_leaf(payload,
    i)`` -> x_flat; the base class handles tree flatten/unflatten, shape
    restore and byte accounting.
    """

    name = "identity"
    stateful = False          # True -> per-client state (error feedback)

    def bind(self, template_tree) -> "Codec":
        """Record the tree structure + leaf shapes the codec operates on."""
        leaves, self._treedef = jax.tree_util.tree_flatten(template_tree)
        self._shapes = [tuple(x.shape) for x in leaves]
        self._dtypes = [jnp.dtype(x.dtype) for x in leaves]
        return self

    def _n(self, i) -> int:
        """Element count of bound leaf ``i``."""
        n = 1
        for d in self._shapes[i]:
            n *= d
        return n

    def _leaves(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self._shapes):
            raise ValueError(f"codec bound to a {len(self._shapes)}-leaf "
                             f"tree, got {len(leaves)} leaves")
        return leaves

    # -- per-leaf hooks -------------------------------------------------
    def _encode_leaf(self, x, state, key, i) -> Tuple[Any, Any]:
        return x, state

    def _encode_leaf_level(self, x, state, key, i, level) -> Tuple[Any, Any]:
        raise NotImplementedError(
            f"codec {self.name!r} does not support level-parameterized "
            "encode (no compression ladder)")

    def _decode_leaf(self, payload, i):
        return payload

    def _init_leaf_state(self, i):
        return ()

    # -- level ladder (adaptive compression, repro.control) -------------
    # Ladder-capable codecs bind once at the top (capacity) level; a
    # traced int32 ``level`` then masks each payload down to the
    # effective rung while the wire buffers keep their static capacity
    # shape under jit.  ``level_bytes`` reports what a real wire would
    # carry per rung, for CommLog's effective-bytes accounting.
    _ladder = None            # ascending effective levels; None -> static

    def set_ladder(self, values) -> "Codec":
        raise ValueError(
            f"codec {self.name!r} has no compression ladder; adaptive "
            "controllers need a ladder-capable uplink codec "
            "(topk/topk_noef/quant/int8/int4)")

    def level_bytes(self) -> Tuple[int, ...]:
        """Effective wire bytes per ladder level (bind + set_ladder first)."""
        raise ValueError(f"codec {self.name!r} has no compression ladder")

    # -- public API -----------------------------------------------------
    def init_state(self, template_tree=None):
        """Fresh per-client codec state (EF residuals; () if stateless)."""
        if template_tree is not None:
            self.bind(template_tree)
        return [self._init_leaf_state(i) for i in range(len(self._shapes))]

    def encode(self, tree, state=None, key=None, level=None):
        """tree -> (payload, new_state).  ``key`` drives stochastic
        rounding / sketch seeds; None selects the deterministic variant.
        ``level`` (a traced int32 scalar) selects the effective rung of a
        bound ladder (``set_ladder``); None encodes at the static
        configuration and traces exactly the pre-ladder program."""
        leaves = self._leaves(tree)
        if state is None:
            state = self.init_state()
        keys = (jax.random.split(key, len(leaves)) if key is not None
                else [None] * len(leaves))
        payload: List[Any] = []
        new_state: List[Any] = []
        for i, (x, s) in enumerate(zip(leaves, state)):
            xf = x.reshape(-1).astype(jnp.float32)
            if level is None:
                p, ns = self._encode_leaf(xf, s, keys[i], i)
            else:
                p, ns = self._encode_leaf_level(xf, s, keys[i], i, level)
            payload.append(p)
            new_state.append(ns)
        return payload, new_state

    def decode(self, payload):
        """payload -> tree (shapes/dtypes of the bound template)."""
        leaves = [self._decode_leaf(p, i).reshape(self._shapes[i])
                  .astype(self._dtypes[i])
                  for i, p in enumerate(payload)]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def roundtrip(self, tree, state=None, key=None, level=None):
        """tree -> (decode(payload), new_state): what the receiver gets,
        with the sender's new state.  The default is ``decode(encode(...))``
        traced as such; a codec whose dense result is cheaper to build
        without the payload overrides it, with the same values bit for
        bit.  Wire bytes still come from ``encode``'s payload."""
        payload, new_state = self.encode(tree, state, key, level=level)
        return self.decode(payload), new_state

    def nbytes(self, payload) -> int:
        """True wire bytes of one payload (sum over transmitted buffers)."""
        return int(sum(_nbytes_of(x)
                       for x in jax.tree_util.tree_leaves(payload)))

    def wire_bytes(self) -> int:
        """Static per-message wire bytes, via an abstract encode."""
        template = jax.tree_util.tree_unflatten(
            self._treedef,
            [jax.ShapeDtypeStruct(s, d)
             for s, d in zip(self._shapes, self._dtypes)])
        k = jax.random.PRNGKey(0) if self.uses_key else None
        payload, _ = jax.eval_shape(
            lambda t: self.encode(t, self.init_state(), k), template)
        return self.nbytes(payload)

    uses_key = False          # True -> encode consumes a PRNG key


class IdentityCodec(Codec):
    """No compression: the payload is the raw fp32 tree (baseline)."""

    name = "identity"
