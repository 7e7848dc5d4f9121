"""Mixture-of-Experts layer with capacity-based gather/scatter dispatch.

TPU-idiomatic design: instead of GShard's [T, E, C] one-hot dispatch tensors
(O(T*E*C) memory), the router computes token->expert top-k assignments and
each expert then gathers its top-C assigned tokens ("expert's choice among
the assigned"), runs the FFN as a batched einsum over [E, C, d] and
scatter-adds the weighted results back.  Memory is O(E*C*d); the gathers and
the [E, C, d] activation shard cleanly over an expert-parallel mesh axis
(tokens move via all-to-all inserted by GSPMD).

Tokens that exceed an expert's capacity are dropped (standard); the router
aux loss (Switch-style load balancing) discourages that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init


def _shard_capacity(x):
    """Constrain [E, C, *] intermediates to shard C over the 'model' axis.

    Under an active mesh (jax.set_mesh), splitting the capacity dim turns
    the w2 row-parallel partial-sum all-reduce into a reduce-scatter and
    parallelises the gather/scatter paths — §Perf hillclimb 3.  No-op when
    there is no mesh, no 'model' axis, or C does not divide.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    if x.shape[1] % mesh.shape["model"]:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(*([None, "model"] + [None] * (x.ndim - 2))))


def moe_init(key, d_model, n_experts, moe_d_ff, act, dtype=jnp.float32,
             dense_residual=False, d_ff=0):
    ks = jax.random.split(key, 6)
    p = {
        "router": dense_init(ks[0], (d_model, n_experts), dtype),
        "w1": dense_init(ks[1], (n_experts, d_model, moe_d_ff), dtype),
        "w2": dense_init(ks[2], (n_experts, moe_d_ff, d_model), dtype),
    }
    if act == "silu":
        p["w3"] = dense_init(ks[3], (n_experts, d_model, moe_d_ff), dtype)
    if dense_residual:
        from repro.models.layers import mlp_init
        p["dense"] = mlp_init(ks[4], d_model, d_ff, act, dtype)
    return p


def moe_apply(params, x, *, top_k, act, capacity_factor=1.25,
              dense_residual=False, full_capacity=False,
              shard_capacity=False):
    """x [B, S, d] -> (out [B, S, d], aux_loss scalar).

    ``full_capacity=True`` sets every expert's capacity to T (no token ever
    dropped) — used by the decode path, where T = B is tiny and dropping the
    single token of a sequence would corrupt generation.
    """
    B, S, d = x.shape
    E = params["router"].shape[1]
    xt = x.reshape(B * S, d)
    T = B * S

    logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    topk_vals, topk_idx = jax.lax.top_k(gates, top_k)          # [T, k]
    assign = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(1)  # [T, E]
    scores = gates * assign                                    # gate if assigned

    # Switch-style load-balance aux loss.
    frac_tokens = assign.mean(axis=0)          # fraction routed to e
    frac_probs = gates.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) / top_k

    # Expert capacity: each expert picks its top-C assigned tokens.
    if full_capacity:
        cap = T
    else:
        cap = int(max(top_k * T / E * capacity_factor, 1))
        cap = min(cap, T)
    w_ec, idx_ec = jax.lax.top_k(scores.T, cap)                # [E, C]

    xe = jnp.take(xt, idx_ec.reshape(-1), axis=0)
    xe = xe.reshape(E, cap, d)                                 # [E, C, d]
    if shard_capacity:
        xe = _shard_capacity(xe)
    h = jnp.einsum("ecd,edf->ecf", xe, params["w1"])
    if act == "silu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xe, params["w3"])
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w2"])           # [E, C, d]
    if shard_capacity:
        ye = _shard_capacity(ye)

    ye = ye * w_ec[..., None].astype(ye.dtype)                 # gate weighting
    out = jnp.zeros((T, d), ye.dtype).at[idx_ec.reshape(-1)].add(
        ye.reshape(E * cap, d))
    out = out.reshape(B, S, d).astype(x.dtype)

    if dense_residual:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(params["dense"], x, act)
    return out, aux


def moe_reference(params, x, *, top_k, act, dense_residual=False):
    """Dense-compute oracle: every expert on every token, exact top-k mix.

    Capacity-free; used by tests as the semantic reference (the production
    path may drop over-capacity tokens, tests use capacity_factor covering
    all tokens so both match).
    """
    B, S, d = x.shape
    E = params["router"].shape[1]
    xt = x.reshape(B * S, d)
    gates = jax.nn.softmax(
        xt.astype(jnp.float32) @ params["router"].astype(jnp.float32), -1)
    topk_vals, topk_idx = jax.lax.top_k(gates, top_k)
    mask = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(1)
    w = gates * mask                                           # [T, E]

    h = jnp.einsum("td,edf->etf", xt, params["w1"])
    if act == "silu":
        h = jax.nn.silu(h) * jnp.einsum("td,edf->etf", xt, params["w3"])
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("etf,efd->etd", h, params["w2"])            # [E, T, d]
    out = jnp.einsum("te,etd->td", w.astype(y.dtype), y)
    out = out.reshape(B, S, d).astype(x.dtype)
    if dense_residual:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(params["dense"], x, act)
    return out
