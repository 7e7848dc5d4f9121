"""Client-parallel ``shard_map`` wrapper for the K-round superstep.

``repro.engine`` runs one jitted superstep per chunk; this module maps
that superstep over the launch mesh so the chunk's client axis — the
embarrassingly-parallel dimension of federated learning — actually runs
in parallel across devices:

* ``batches [K, C, ...]`` / ``sizes [K, C]`` are sharded over the client
  mesh axes (``pod`` then ``data``): shard ``s`` trains sampled positions
  ``[s*C_loc, (s+1)*C_loc)`` of every round in the chunk;
* the full-federation EF table is row-sharded by client id in the
  RESIDENT scratch-row layout (shard ``s`` holds its ``N_loc`` owned rows
  plus one permanent write-sink row — ``repro.launch.sharding.
  ef_table_sharding``), so the per-round scatter is one in-place aliased
  row write instead of a concatenate/slice pair.  Under the cohort-paged
  store (``ef_store="host"``) the same specs carry a chunk-local PAGE
  (``[(K*C+1)*S, ...]`` — per-shard slot blocks with the identical
  scratch row) and ``cids`` carries page-relative virtual ids whose
  shard assignment is ``cid % S``; the superstep body is unchanged;
* global state, broadcast mirror, lr schedule, round keys and ``cids``
  are replicated — every shard computes the identical server-side update
  from the psum'd aggregate, so the replicated outputs agree bitwise
  across shards;
* the eval batch is split positionally over the client axes when the
  evaluator is shard-aware (``eval_sharded=True``, the engine default —
  eval-every-round then costs S× less compute), or replicated for a
  plain evaluator;
* cross-device traffic per round is ONE packed psum with
  ``fused_collective=True`` (the default: FedAvg aggregate + EF exchange
  + pipelined weight totals in a single flat-buffer all-reduce — see
  ``repro.engine.superstep``), or the three-collective oracle layout with
  ``fused_collective=False``.

The mesh's ``model`` axis (if any) is treated as replicated: the engine's
CNN-scale federated workloads are client-bound, and tensor parallelism
inside a client step remains the territory of ``repro.launch.steps``.

Everything here is layout only — the math lives in the shard-aware round
fns (``repro.core.rounds``) and superstep bodies.  A mesh whose client
axes multiply to 1 must NOT go through this wrapper: the engine keeps the
plain superstep there so single-device runs stay bitwise-equal to the
reference loop.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.core.aggregate import ClientSharding
from repro.engine.superstep import (make_compressed_superstep,
                                    make_plain_superstep)
from repro.launch.mesh import client_axes
from repro.launch.sharding import (chunk_shardings,  # noqa: F401 (re-export)
                                   ef_table_sharding, eval_batch_sharding)


def client_sharding(mesh) -> Optional[ClientSharding]:
    """The mesh's client-axis split, or None when it degenerates to 1."""
    axes = client_axes(mesh)
    sizes = tuple(mesh.shape[a] for a in axes)
    n = 1
    for s in sizes:
        n *= s
    if n <= 1:
        return None
    return ClientSharding(axes=axes, sizes=sizes)


def _unchecked_shard_map(fn, mesh, in_specs, out_specs):
    # check_vma off: outputs marked replicated are made identical
    # on every shard by construction (they are functions of replicated
    # inputs and psum results), which the static replication checker
    # cannot see through the scan carry.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_sharded_superstep(bundle, fl, mode, n_rounds, mesh, *,
                           uplink=None, downlink=None, eval_fn=None,
                           impl="auto", fused_collective=True,
                           eval_sharded=True, telemetry=None,
                           participation=False, controller=None,
                           inner_wrap=None):
    """``shard_map``-wrapped superstep on ``mesh`` (client axes size > 1).

    Same call signature as the unsharded supersteps; the plain variant is
    built when ``uplink`` is None, the codec-routed one otherwise.  The
    caller stages batches/sizes with
    :func:`repro.launch.sharding.chunk_shardings` and the EF table with
    :func:`repro.launch.sharding.ef_table_sharding` (resident scratch-row
    layout); jit with the same donations as the unsharded path.

    ``eval_fn`` must match ``eval_sharded``: a shard-aware evaluator
    (``make_eval_fn(shard=client_sharding(mesh))`` fed a batch padded
    with ``pad_eval_batch(shard=...)`` and staged with
    :func:`repro.launch.sharding.eval_batch_sharding`) when True, a
    replicated one when False.

    ``inner_wrap`` is an analyzer hook (``repro.analysis``): a callable
    applied to the superstep BODY before the ``shard_map`` wrap, i.e.
    inside the mesh context but outside jit.  The invariant analyzer's
    mutation tests use it to seed deliberate violations (a second psum,
    an f64 cast, a host callback) and prove the passes catch them; it
    must preserve the superstep signature.  Production callers leave it
    None.
    """
    shard = client_sharding(mesh)
    if shard is None:
        raise ValueError("use the plain superstep on a 1-shard mesh "
                         "(client axes multiply to 1)")
    ax = shard.axis_name
    test_spec = P(ax) if eval_sharded else P()
    n_test = 2 if eval_fn is not None else 0
    # pmask/pstale [K, C] split over the client axes, exactly like sizes
    part_specs = (P(None, ax), P(None, ax)) if participation else ()

    if uplink is None:
        inner = make_plain_superstep(bundle, fl, mode, n_rounds,
                                     eval_fn=eval_fn, impl=impl,
                                     shard=shard, fused=fused_collective,
                                     telemetry=telemetry,
                                     participation=participation)
        in_specs = (P(), P(None, ax), P(None, ax), P()) \
            + part_specs + (test_spec,) * n_test
        out_specs = (P(), P())
    else:
        inner = make_compressed_superstep(bundle, fl, mode, n_rounds,
                                          uplink, downlink, eval_fn=eval_fn,
                                          impl=impl, shard=shard,
                                          fused=fused_collective,
                                          telemetry=telemetry,
                                          participation=participation,
                                          controller=controller)
        # controller state: replicated scalars in, replicated scalars out
        # (the decision is a function of psum'd taps, identical per shard)
        ctrl_specs = (P(),) if controller is not None else ()
        in_specs = (P(), P(ax), P(), P(None, ax), P(None, ax),
                    P(), P(), P(), P()) + part_specs + ctrl_specs \
            + (test_spec,) * n_test
        out_specs = (P(), P(), P(ax), P()) + ctrl_specs

    if inner_wrap is not None:
        inner = inner_wrap(inner)
    return _unchecked_shard_map(inner, mesh, in_specs, out_specs)


def make_sharded_eval(eval_fn, mesh):
    """``shard_map``-wrap a shard-aware evaluator for boundary dispatch.

    ``eval_fn`` is a :func:`repro.engine.make_eval_fn` built with
    ``shard=client_sharding(mesh)``; the state is replicated, the padded
    batch/mask arrive positionally split over the client axes, and the
    psum'd metrics come back replicated.  The caller jits the result.
    """
    shard = client_sharding(mesh)
    if shard is None:
        raise ValueError("sharded eval needs client axes > 1")
    ax = shard.axis_name
    return _unchecked_shard_map(eval_fn, mesh,
                                in_specs=(P(), P(ax), P(ax)),
                                out_specs=P())
