"""Top-k magnitude sparsification with client-side error feedback.

Per leaf (flattened, k = max(1, round(frac * n))): transmit the k largest-
magnitude entries as (int32 index, fp32 value) pairs — wire bytes = 8k
vs 4n raw.  With error feedback (memory of what compression dropped, added
back before the next encode) non-IID convergence stays close to the
uncompressed baseline at aggressive sparsity, which is what lets the
bytes-to-milestone metric actually improve.

``encode`` builds the payload with ``lax.top_k``.  The round consumes
``roundtrip``, which selects the same k entries without a sort and builds
the decoded leaf and the residual densely (``where(sel, g, 0)`` and
``where(sel, 0, g)``), bit for bit what ``decode(encode(...))`` and
``g.at[idx].set(0)`` give.  The selection is exact: for non-negative
floats the int32 bits of ``|g|`` order as the floats do (+NaN above +inf,
as ``lax.top_k`` orders them), so a bisection over those bits that counts
``bits >= mid`` finds the k-th largest magnitude ``t`` in 31 passes.
Every entry above ``t`` is kept; of those at ``t``, the first
``k - count(bits > t)`` by index, which is how ``lax.top_k`` breaks ties
(a second bisection, over the index, that runs only while some leaf holds
more ties at ``t`` than it keeps).  The ``topk_select`` Pallas kernel
(``ops.topk_threshold_select``) is not this selection: it keeps every
entry at ``|x| >= t``, so all ties, and can keep more than k.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compress.codec import Codec

_BIT_PASSES = 31          # the int32 bits of |g| lie in [0, 2**31 - 1]


def _count(mask):
    return jnp.sum(mask, dtype=jnp.int32)


def _select_top(gs, ks):
    """Masks of the ``ks[i]`` largest ``|gs[i]|`` of each 1-D leaf, ties to
    the lower index, as ``lax.top_k`` selects them; ``ks`` may be traced.
    All leaves share one loop, so a small leaf adds no passes of its own."""
    bits = [jax.lax.bitcast_convert_type(jnp.abs(g), jnp.int32) for g in gs]
    ks = [jnp.asarray(k, jnp.int32) for k in ks]

    # t = the largest value with count(bits >= t) >= k.  The carry keeps
    # ge = count(bits >= lo) and gt = count(bits > hi); they meet at t.
    def value_pass(_, carry):
        out = []
        for b, k, (lo, hi, ge, gt) in zip(bits, ks, carry):
            span = hi - lo
            mid = lo + (span >> 1) + (span & 1)    # ceil, never overflows
            c = _count(b >= mid)
            ok = c >= k
            out.append((jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1),
                        jnp.where(ok, c, ge), jnp.where(ok, gt, c)))
        return out

    init = [(jnp.int32(0), jnp.int32(2 ** 31 - 1), jnp.int32(b.shape[0]),
             jnp.int32(0)) for b in bits]
    found = jax.lax.fori_loop(0, _BIT_PASSES, value_pass, init)

    # keep the first r = k - gt entries at t: the least index bound m with
    # count(bits == t, index < m) >= r; m = n where every tie is kept
    iotas = [jax.lax.iota(jnp.int32, b.shape[0]) for b in bits]

    def tie_pass(carry):
        out = []
        for b, i, (t, _, _, gt), k, (lo, hi) in zip(bits, iotas, found, ks,
                                                    carry):
            mid = lo + ((hi - lo) >> 1)
            ok = _count((b == t) & (i < mid)) >= k - gt
            out.append((jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)))
        return out

    def unsettled(carry):
        return jnp.any(jnp.stack([lo < hi for lo, hi in carry]))

    n = [jnp.int32(b.shape[0]) for b in bits]
    bounds = jax.lax.while_loop(
        unsettled, tie_pass,
        [(jnp.where(ge > k, k - gt, m), m)
         for (_, _, ge, gt), k, m in zip(found, ks, n)])
    return [(b > t) | ((b == t) & (i < m))
            for b, i, (t, _, _, _), (m, _) in zip(bits, iotas, found, bounds)]


class TopKCodec(Codec):
    """Keep the top ``frac`` fraction of entries per leaf (by |value|)."""

    uses_key = False

    def __init__(self, frac: float = 0.05, *, error_feedback: bool = True,
                 impl: str = "auto"):
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"topk frac={frac!r} must be in (0, 1]")
        self.frac = frac
        self.error_feedback = error_feedback
        self.stateful = error_feedback
        self.impl = impl
        self.name = "topk" if error_feedback else "topk_noef"

    def _k(self, i) -> int:
        return max(1, int(round(self.frac * self._n(i))))

    def _init_leaf_state(self, i):
        if not self.error_feedback:
            return ()
        return jnp.zeros((self._n(i),), jnp.float32)

    def _encode_leaf(self, x, state, key, i):
        g = x + state if self.error_feedback else x
        _, idx = jax.lax.top_k(jnp.abs(g), self._k(i))
        idx = idx.astype(jnp.int32)
        val = jnp.take(g, idx)
        payload = {"idx": idx, "val": val.astype(jnp.float32)}
        new_state = g.at[idx].set(0.0) if self.error_feedback else state
        return payload, new_state

    def _decode_leaf(self, payload, i):
        dense = jnp.zeros((self._n(i),), jnp.float32)
        return dense.at[payload["idx"]].set(payload["val"])

    def roundtrip(self, tree, state=None, key=None, level=None):
        """``decode(encode(...))`` and the new residual, bit for bit, built
        densely from a sort-free selection (see the module docstring)."""
        if state is None:
            state = self.init_state()
        xs = [x.reshape(-1).astype(jnp.float32) for x in self._leaves(tree)]
        gs = ([x + s for x, s in zip(xs, state)] if self.error_feedback
              else xs)
        ks = [self._k(i) if level is None
              else jnp.take(self._k_table(i), level)
              for i in range(len(gs))]
        with jax.named_scope("topk_select"):
            sels = _select_top(gs, ks)
        decoded = [jnp.where(sel, g, 0.0).reshape(self._shapes[i])
                   .astype(self._dtypes[i])
                   for i, (g, sel) in enumerate(zip(gs, sels))]
        new_state = ([jnp.where(sel, 0.0, g) for g, sel in zip(gs, sels)]
                     if self.error_feedback else state)
        return (jax.tree_util.tree_unflatten(self._treedef, decoded),
                new_state)

    # -- level ladder ---------------------------------------------------
    def set_ladder(self, values):
        vals = tuple(float(v) for v in values)
        if not vals or list(vals) != sorted(set(vals)):
            raise ValueError(f"ladder {values!r} must be strictly ascending")
        if not all(0.0 < v <= 1.0 for v in vals):
            raise ValueError(f"ladder {values!r} needs fracs in (0, 1]")
        if vals[-1] != self.frac:
            raise ValueError(f"ladder top {vals[-1]} must equal the codec's "
                             f"capacity frac {self.frac}")
        self._ladder = vals
        return self

    def _k_table(self, i):
        return jnp.asarray([max(1, int(round(f * self._n(i))))
                            for f in self._ladder], jnp.int32)

    def _encode_leaf_level(self, x, state, key, i, level):
        g = x + state if self.error_feedback else x
        k_cap = self._k(i)
        _, idx = jax.lax.top_k(jnp.abs(g), k_cap)
        idx = idx.astype(jnp.int32)
        # lax.top_k sorts by magnitude, so the first k_l slots ARE the
        # exact top-k_l payload; the mask zeroes the rest of the
        # capacity-shaped buffer (static wire shape under jit).
        keep = (jnp.arange(k_cap, dtype=jnp.int32)
                < jnp.take(self._k_table(i), level))
        val = jnp.where(keep, jnp.take(g, idx), 0.0)
        payload = {"idx": idx, "val": val.astype(jnp.float32)}
        if self.error_feedback:
            # masked-out slots scatter their own value back: the residual
            # keeps exactly what the effective level did not transmit
            new_state = g.at[idx].set(
                jnp.where(keep, 0.0, jnp.take(g, idx)))
        else:
            new_state = state
        return payload, new_state

    def level_bytes(self):
        if self._ladder is None:
            raise ValueError("set_ladder first")
        return tuple(sum(8 * max(1, int(round(f * self._n(i))))
                         for i in range(len(self._shapes)))
                     for f in self._ladder)
