"""Expert-parallel MoE with explicit shard_map all-to-all token dispatch.

§Perf (EXPERIMENTS.md, hillclimb 3 iter 2) showed that annotating the
capacity axis cannot fix the MoE collective term: the gather/scatter
anchor the sharding and GSPMD re-inserts the giant all-reduce.  The real
fix is restructuring the dispatch — each data shard routes its OWN tokens,
sends only its top-C picks per expert to the expert's home shard via
``lax.all_to_all`` (the top-k/E activation fraction), and receives the
results back.  This module implements that as a drop-in alternative to
``moe.moe_apply``.

Layout inside ``shard_map`` over the ``data`` axis (n_sh shards):
    tokens   x      [T_loc, d]           (sharded)
    experts  w1/w2  [E_loc, ...]         (sharded; E = n_sh * E_loc)
    router          [d, E]               (replicated)

Per shard:
  1. route local tokens, per-expert top-C pick  -> xe [E, C, d]
  2. all_to_all (send dim = expert home shard)  -> recv [n_sh, E_loc, C, d]
  3. local expert FFN over [E_loc, n_sh*C, d]
  4. all_to_all back, apply gate weights at the source, scatter-add.

Communication per shard: 2 * top_k/E-ish * T_loc * d * capacity_factor —
vs the replicated-expert all-reduce of the FULL [E, C, d] activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import mlp_apply

# Concrete mesh for shard_map, set by the launch layer before tracing
# (jax.sharding.get_mesh() is unavailable inside jit; the model call stack
# does not thread the mesh, so the launcher registers it here).
_DISPATCH_MESH = None


def set_dispatch_mesh(mesh) -> None:
    global _DISPATCH_MESH
    _DISPATCH_MESH = mesh


def _local_moe(xt, router, w1, w2, w3, *, top_k, act, capacity_factor,
               axis, mean_axes=None, tp_psum=False):
    """Per-shard body (runs under shard_map).  xt [T_loc, d]."""
    T, d = xt.shape
    E = router.shape[1]
    n_sh = jax.lax.axis_size(axis)
    E_loc = w1.shape[0]
    if E != n_sh * E_loc:
        raise ValueError(f"router has {E} experts but {n_sh} shards x "
                         f"{E_loc} local experts")

    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    topk_vals, topk_idx = jax.lax.top_k(gates, top_k)
    assign = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(1)
    scores = gates * assign

    # load-balance aux (Switch), averaged over ALL token shards (incl. pod)
    mean_axes = mean_axes or axis
    frac_tokens = jax.lax.pmean(assign.mean(axis=0), mean_axes)
    frac_probs = jax.lax.pmean(gates.mean(axis=0), mean_axes)
    aux = E * jnp.sum(frac_tokens * frac_probs) / top_k

    # per-SOURCE-shard capacity per expert
    cap = int(max(top_k * T / E * capacity_factor, 1))
    cap = min(cap, T)
    w_ec, idx_ec = jax.lax.top_k(scores.T, cap)                 # [E, C]

    xe = jnp.take(xt, idx_ec.reshape(-1), axis=0).reshape(E, cap, d)
    # group by expert home shard and exchange
    xe = xe.reshape(n_sh, E_loc, cap, d)
    xe_recv = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=0,
                                 tiled=False)                   # [n_sh,E_loc,C,d]

    # local expert FFN over all received tokens.  w1/w3 arrive with the
    # FFN dim additionally sharded over 'model' (EP x TP): each model shard
    # computes its f/|model| slice and the w2 partial sums are psum'd —
    # without this the model axis idles during MoE and per-chip FLOPs
    # blow up by |model| (measured: t_comp 20.9 s -> 65.2 s on arctic).
    xw = xe_recv.transpose(1, 0, 2, 3).reshape(E_loc, n_sh * cap, d)
    h = jnp.einsum("ecd,edf->ecf", xw, w1)
    if act == "silu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xw, w3)
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, w2)                      # [E_loc,nshC,d]
    if tp_psum:
        ye = jax.lax.psum(ye, "model")

    # send results back to the source shards
    ye = ye.reshape(E_loc, n_sh, cap, d).transpose(1, 0, 2, 3)  # [n_sh,E_loc,C,d]
    ye_back = jax.lax.all_to_all(ye, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
    ye_back = ye_back.reshape(E, cap, d)

    # gate-weight at the source and scatter-add into token order
    ye_back = ye_back * w_ec[..., None].astype(ye_back.dtype)
    out = jnp.zeros((T, d), ye_back.dtype).at[idx_ec.reshape(-1)].add(
        ye_back.reshape(E * cap, d))
    return out.astype(xt.dtype), aux


def moe_apply_a2a(params, x, mesh=None, *, top_k, act, capacity_factor=1.25,
                  dense_residual=False, axis="data"):
    """Expert-parallel MoE forward with all-to-all dispatch.

    ``params`` as produced by ``moe.moe_init``; the expert tensors must be
    sharded over ``axis`` on dim 0 (param_shardings with ep=True does
    this).  x [B, S, d] sharded over ``axis`` on dim 0.
    Returns (out [B, S, d], aux scalar) — semantics of ``moe.moe_apply``.
    """
    if mesh is None:
        mesh = _DISPATCH_MESH
    if mesh is None:
        raise ValueError("moe_dispatch='a2a' needs a concrete mesh: call "
                         "moe_dispatch.set_dispatch_mesh(mesh) before "
                         "tracing (steps.build_* does this)")
    B, S, d = x.shape
    has_w3 = "w3" in params
    batch_axes = tuple(a for a in ("pod", axis) if a in mesh.axis_names)

    def body(xb, router, w1, w2, w3):
        xt = xb.reshape(-1, d)
        out, aux = _local_moe(xt, router, w1, w2, w3, top_k=top_k, act=act,
                              capacity_factor=capacity_factor, axis=axis,
                              mean_axes=batch_axes,
                              tp_psum="model" in mesh.axis_names)
        return out.reshape(xb.shape), aux

    w3 = params["w3"] if has_w3 else jnp.zeros_like(params["w1"])
    tp = "model" if "model" in mesh.axis_names else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes), P(), P(axis, None, tp), P(axis, tp, None),
                  P(axis, None, tp)),
        out_specs=(P(batch_axes), P()),
        check_vma=False)
    out, aux = fn(x, params["router"], params["w1"], params["w2"], w3)
    if dense_residual:
        out = out + mlp_apply(params["dense"], x, act)
    return out, aux
