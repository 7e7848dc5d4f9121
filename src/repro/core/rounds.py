"""One federated round as a single jit-able step function.

Two execution modes map the round onto the device mesh (DESIGN.md §4):

* ``client_parallel`` — vmap over the round's clients; the client axis of
  the batch is sharded over the mesh's ``data`` (and ``pod``) axes, so each
  data-group trains one client's replica and the final weighted average is
  the only cross-group collective (exactly the communication FedAvg counts).

* ``client_sequential`` — ``lax.scan`` over clients with a running weighted
  parameter sum; a single (FSDP/expert-sharded) model instance lives at a
  time, and the batch *within* a client is sharded over ``data``.

Both return (new_global_state, metrics).  ``global_state`` is
``{'model': params, **extras}`` where ``extras`` are the algorithm
plugin's ``Algorithm.extra_state`` entries (FedFusion's fusion params;
empty for single-stream algorithms).  The round fns thread and
accumulate those extras generically — what they *mean* lives in the
plugin's ``aggregate_extras`` / ``finalize_extra_sums`` hooks, so a new
mechanism registers with ``repro.fl.api`` and rides through here without
edits.

Engine contract (``repro.engine``): the superstep ``lax.scan``s these
round fns over a chunk of pre-staged rounds, so they must stay *pure*
functions of their arguments with a stable output structure — state and
metrics shapes cannot depend on data, and everything that varies per
round (batches, sizes, lr, sampled cids, the fold_in round key) arrives
as an argument, never from Python-level state.  For the compressed fn the
returned broadcast (4th output) IS the clients' next downlink mirror; the
engine threads it and the per-client EF rows through the scan carry and
scatters the EF rows back into the device-resident full-federation table
(``ops.ef_scatter``).

Sharding contract (``repro.engine.sharded``): with ``shard`` — a
:class:`repro.core.aggregate.ClientSharding` — the round fn is a
``shard_map`` BODY: its client axis holds only this shard's slice of the
round's clients (positional split: shard s trains sampled positions
``[s*C_loc, (s+1)*C_loc)``), every per-client quantity (local training,
codec encode/decode, EF rows) stays shard-local, and the only collectives
are the in-shard-reduce + single ``psum`` aggregations in
``repro.core.aggregate`` / the plugin's ``aggregate_extras``.  Replicated
inputs (global model, mirror, round key, lr) produce bitwise-identical
replicated outputs on every shard because the psum results agree
everywhere.  With ``shard=None`` the code path is exactly the
pre-sharding one — no collectives — which is what keeps the
single-device engine bitwise-equal to the reference loop.

Fused-collective contract (``repro.engine.superstep`` with
``fused_collective=True``): the ``*_round_parts`` factories split a round
into a *local* function — everything up to and including this shard's
weighted contribution sums, no collectives — and a *finish* function that
consumes the psum-completed sums.  The superstep packs the local sums
into ONE flat buffer together with the EF exchange and the next round's
weight total and runs a single ``psum``
(:func:`repro.core.aggregate.fused_psum`).  The split keeps every
arithmetic op of the unfused path (weights are normalized against a
total psummed one round ahead — the sizes are pre-staged inputs, so the
value is identical; extras close through ``finalize_extra_sums``, whose
ops equal the in-tree plugins' ``aggregate_extras`` after the weighted
sum), which is what makes fused and unfused rounds bitwise-equal.

Participation contract (``repro.fl.participation``): with
``participation=True`` every factory's round fn takes two extra
``[n_clients]`` float32 inputs — ``pmask`` (0/1 contribution mask) and
``pstale`` (staleness, telemetry only).  Masked clients are zeroed
purely *by weight*: the engine pre-multiplies the staged sizes by
``mask * staleness_weight`` on the host, so the existing normalized
weighted mean — including the fused path's pipelined total — silently
excludes them with no shape changes and no extra collectives.  The
round-level additions are (a) the per-client EF update is guarded so a
masked client's residual is carried forward untouched (its payload
never reached the server, so its dropped mass must stay local), and
(b) the round loss becomes the mask-weighted mean, its numerator /
denominator riding the round's existing collective and dividing in the
post-psum finish step.  With ``participation=False`` (the default)
every traced code path is byte-identical to before this axis existed.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core.aggregate import (ClientSharding, client_weighted_sum,
                                  finish_masked_loss,
                                  masked_loss_sums, mean_over_clients,
                                  normalize_weights, psum_tree,
                                  running_update, weights_over_total,
                                  zeros_like_tree)
from repro.core.local import _algorithm, make_local_trainer
from repro.models.registry import ModelBundle
from repro.obs.scopes import AGGREGATE, CODEC, in_part, part
# repro.obs sits at the bottom of the import graph (jax only) — no cycle
from repro.obs.telemetry import ClientTapCtx


def _local_client_keys(key, n_local: int, shard: Optional[ClientSharding]):
    """Per-client rng keys for THIS shard's clients.

    The reference loop splits the round key over the full C sampled
    clients in positional order; a shard must use the identical keys for
    its positional slice, so the full split is computed (replicated — it
    is a few dozen uint32s) and dynamically sliced at the shard offset.
    """
    if shard is None:
        return jax.random.split(key, n_local)
    full = jax.random.split(key, n_local * shard.n_shards)
    start = (shard.position() * n_local).astype(jnp.int32)
    return jax.lax.dynamic_slice_in_dim(full, start, n_local, axis=0)


_RESERVED_CONTRIB_KEYS = frozenset(("model", "delta", "loss", "lsum", "lw",
                                    "tele"))


@in_part(AGGREGATE)
def _sum_clients(tele):
    """[C]-stacked per-client tap sums -> this shard's scalar sums (the
    psum-pending half of the round's telemetry; {} passes through)."""
    return {k: jnp.sum(v, axis=0) for k, v in tele.items()}


def _check_extra_keys(extra_keys):
    """The fused-collective contribution dicts key the model/delta sums
    and the chunk loss alongside the plugin's extras — an extra named
    after one of those would be silently clobbered, so fail at build
    time instead."""
    clash = _RESERVED_CONTRIB_KEYS.intersection(extra_keys)
    if clash:
        raise ValueError(
            f"Algorithm.extra_state keys {sorted(clash)} collide with the "
            f"round accumulators' reserved keys {sorted(_RESERVED_CONTRIB_KEYS)}"
            f" — rename the extra state entries")


def _weighted_sums(stacked, weights):
    """Weighted client sum of a leading-client-axis tree — the in-shard
    half of :func:`repro.core.aggregate.weighted_mean` (psum completes
    it)."""
    return client_weighted_sum(weights, stacked)


def _make_plain_clients(bundle: ModelBundle, fl: FLConfig, mode: str, *,
                        impl="auto", telemetry=None):
    """Shared client-side computation of one uncompressed round.

    Returns ``run_clients(global_state, client_batches, weights, lr,
    n_examples) -> (wsums, stacked_extras, losses, tele)``: ``wsums``
    holds this shard's weighted sums ``{"model": tree, **extras}``
    (psum-pending), ``stacked_extras`` the per-client extras
    (client_parallel only; the sequential scan only materializes the
    running sums), and ``tele`` this shard's telemetry tap sums
    (psum-pending scalars; ``{}`` with ``telemetry=None`` — the code path
    is then byte-identical to the untapped one).

    ``pmask``/``pstale`` (participation mask + staleness, ``None`` when
    the participation axis is off) feed the telemetry tap contexts only:
    plain-round masking itself is entirely weight-borne (the engine
    zeroes masked clients' example weights on the host), so with
    ``telemetry=None`` the traced computation never sees them.
    """
    if mode not in ("client_parallel", "client_sequential"):
        raise ValueError(f"unknown fl mode {mode!r}")
    algo = _algorithm(fl)
    trainer = make_local_trainer(bundle, fl, impl=impl)
    extra_keys = algo.extra_state

    def run_clients(global_state, client_batches, weights, lr,
                    n_examples=None, pmask=None, pstale=None):
        gm = global_state["model"]
        gx = algo.extra_from_state(global_state)

        if mode == "client_parallel":
            if telemetry is None:
                def train_one(batches):
                    return trainer(gm, gx, batches, lr)

                trainables, losses = jax.vmap(train_one)(client_batches)
                tele = {}
            elif pmask is None:
                def train_one(batches, nex):
                    trainable, loss = trainer(gm, gx, batches, lr)
                    t = telemetry.client_sums(ClientTapCtx(
                        n_examples=nex, loss=loss,
                        model=trainable["model"], global_model=gm))
                    return trainable, loss, t

                trainables, losses, tele_c = jax.vmap(train_one)(
                    client_batches, n_examples)
                tele = _sum_clients(tele_c)
            else:
                def train_one(batches, nex, m, st):
                    trainable, loss = trainer(gm, gx, batches, lr)
                    t = telemetry.client_sums(ClientTapCtx(
                        n_examples=nex, loss=loss,
                        model=trainable["model"], global_model=gm,
                        pmask=m, staleness=st))
                    return trainable, loss, t

                trainables, losses, tele_c = jax.vmap(train_one)(
                    client_batches, n_examples, pmask, pstale)
                tele = _sum_clients(tele_c)
            wsums = {"model": _weighted_sums(trainables["model"], weights)}
            for k in extra_keys:
                wsums[k] = _weighted_sums(trainables[k], weights)
            return (wsums, {k: trainables[k] for k in extra_keys}, losses,
                    tele)

        acc0 = {"model": zeros_like_tree(gm)}
        for k in extra_keys:
            acc0[k] = zeros_like_tree(global_state[k])

        if telemetry is None:
            def body(acc, xs):
                batches, w = xs
                trainable, loss = trainer(gm, gx, batches, lr)
                # accumulate the weighted client params (and extras — e.g.
                # fusion gates; the plugin's EMA etc. applies after the sum)
                acc = {k: running_update(acc[k], trainable[k], w)
                       for k in acc}
                return acc, loss

            acc, losses = jax.lax.scan(body, acc0, (client_batches, weights))
            return acc, None, losses, {}

        if pmask is None:
            def body(acc, xs):
                batches, w, nex = xs
                trainable, loss = trainer(gm, gx, batches, lr)
                acc = {k: running_update(acc[k], trainable[k], w)
                       for k in acc}
                t = telemetry.client_sums(ClientTapCtx(
                    n_examples=nex, loss=loss, model=trainable["model"],
                    global_model=gm))
                return acc, (loss, t)

            acc, (losses, tele_c) = jax.lax.scan(
                body, acc0, (client_batches, weights, n_examples))
            return acc, None, losses, _sum_clients(tele_c)

        def body(acc, xs):
            batches, w, nex, m, st = xs
            trainable, loss = trainer(gm, gx, batches, lr)
            acc = {k: running_update(acc[k], trainable[k], w) for k in acc}
            t = telemetry.client_sums(ClientTapCtx(
                n_examples=nex, loss=loss, model=trainable["model"],
                global_model=gm, pmask=m, staleness=st))
            return acc, (loss, t)

        acc, (losses, tele_c) = jax.lax.scan(
            body, acc0, (client_batches, weights, n_examples, pmask, pstale))
        return acc, None, losses, _sum_clients(tele_c)

    return run_clients


def make_round_fn(bundle: ModelBundle, fl: FLConfig, mode: str, *,
                  impl="auto", shard: Optional[ClientSharding] = None,
                  telemetry=None, participation=False):
    """Returns round_fn(global_state, client_batches, n_examples, lr).

    ``client_batches``: pytree with leading dims [n_clients, local_steps, ...].
    ``n_examples``: [n_clients] float (n_t weighting).
    Under ``shard`` both carry only this shard's clients.

    ``telemetry`` (a :class:`repro.obs.telemetry.Telemetry`) adds
    ``tele/...`` entries to the round metrics; the tap sums ride the
    aggregation psum the round already performs (``psum`` of a tree is one
    collective regardless of leaf count, and elementwise reduction keeps
    the pre-existing leaves' bits), so the round stays one-psum and
    bitwise-equal to the untapped build.

    ``participation=True`` appends ``pmask``/``pstale`` [n_clients]
    inputs (see module docstring): ``n_examples`` then arrives already
    mask-and-staleness-weighted from the host, and the round loss is the
    mask-weighted mean whose sums ride the same psum.
    """
    algo = _algorithm(fl)
    extra_keys = algo.extra_state
    run_clients = _make_plain_clients(bundle, fl, mode, impl=impl,
                                      telemetry=telemetry)

    @in_part(AGGREGATE)
    def _finish(global_state, summed, stacked_extras, weights):
        if mode == "client_parallel":
            new_state: Dict[str, Any] = {"model": summed["model"]}
            new_state.update(algo.aggregate_extras(fl, global_state,
                                                   stacked_extras, weights,
                                                   shard=shard))
        else:
            new_state = {"model": summed["model"]}
            new_state.update(algo.finalize_extra_sums(
                fl, global_state, {k: summed[k] for k in extra_keys}))
        return new_state

    if participation:
        def round_fn(global_state, client_batches, n_examples, lr,
                     pmask, pstale):
            weights = normalize_weights(n_examples, shard)
            wsums, stacked_extras, losses, tele = run_clients(
                global_state, client_batches, weights, lr, n_examples,
                pmask, pstale)
            lsums = masked_loss_sums(losses, pmask)
            if mode == "client_parallel":
                summed = psum_tree(
                    {"model": wsums["model"], "tele": tele, **lsums}, shard)
            else:
                summed = psum_tree({**wsums, "tele": tele, **lsums}, shard)
            new_state = _finish(global_state, summed, stacked_extras,
                                weights)
            metrics = {"local_loss": finish_masked_loss(summed)}
            if telemetry is not None:
                metrics.update(telemetry.finish(summed["tele"]))
            return new_state, metrics

        return round_fn

    def round_fn(global_state, client_batches, n_examples, lr):
        weights = normalize_weights(n_examples, shard)
        wsums, stacked_extras, losses, tele = run_clients(
            global_state, client_batches, weights, lr, n_examples)
        if mode == "client_parallel":
            # tele rides the model-sum psum: same single collective
            summed = psum_tree({"model": wsums["model"], "tele": tele},
                               shard)
        else:
            # the running sums covered this shard's clients; one psum per
            # tree completes them over the round (no-op when unsharded)
            summed = psum_tree({**wsums, "tele": tele}, shard)
        new_state = _finish(global_state, summed, stacked_extras, weights)
        metrics = {"local_loss": mean_over_clients(losses, shard)}
        if telemetry is not None:
            metrics.update(telemetry.finish(summed["tele"]))
        return new_state, metrics

    return round_fn


def make_round_parts(bundle: ModelBundle, fl: FLConfig, mode: str, *,
                     impl="auto", shard: ClientSharding, telemetry=None,
                     participation=False):
    """Deferred-psum split of :func:`make_round_fn` (fused collectives).

    Returns ``(local_fn, finish_fn)``:

    ``local_fn(global_state, client_batches, total, n_examples, lr) ->
    contribs`` — this shard's psum-pending contributions ``{"model": tree,
    **extras, "loss": scalar}``.  ``total`` is the round's psum-completed
    example count (the superstep pipelines it one collective ahead, since
    sizes are pre-staged inputs); dividing by it reproduces
    ``normalize_weights`` bit for bit.

    ``finish_fn(global_state, summed) -> (new_state, metrics)`` consumes
    the psum-completed contributions.  Extras close through the plugin's
    ``finalize_extra_sums`` — for weighted-sum-then-postprocess
    aggregations (every in-tree plugin) that is op-for-op the tail of
    ``aggregate_extras``, keeping fused == unfused bitwise.

    ``telemetry`` taps contribute a ``"tele"`` sub-dict to ``contribs`` —
    a few extra f32 scalars riding the superstep's single fused psum —
    and their finalized ``tele/...`` metrics to ``finish_fn``'s output.

    ``participation=True``: ``local_fn`` takes trailing ``pmask``/
    ``pstale`` inputs, the mask-weighted loss sums replace the plain
    chunk-loss scalar in ``contribs`` (two f32 lanes on the same fused
    psum), and ``finish_fn`` divides them post-psum.
    """
    algo = _algorithm(fl)
    extra_keys = algo.extra_state
    _check_extra_keys(extra_keys)
    run_clients = _make_plain_clients(bundle, fl, mode, impl=impl,
                                      telemetry=telemetry)

    if participation:
        def local_fn(global_state, client_batches, total, n_examples, lr,
                     pmask, pstale):
            weights = weights_over_total(n_examples, total)
            wsums, _, losses, tele = run_clients(
                global_state, client_batches, weights, lr, n_examples,
                pmask, pstale)
            return {**wsums, **masked_loss_sums(losses, pmask),
                    "tele": tele}
    else:
        def local_fn(global_state, client_batches, total, n_examples, lr):
            weights = weights_over_total(n_examples, total)
            wsums, _, losses, tele = run_clients(
                global_state, client_batches, weights, lr, n_examples)
            return {**wsums, "loss": mean_over_clients(losses), "tele": tele}

    @in_part(AGGREGATE)
    def finish_fn(global_state, summed):
        new_state: Dict[str, Any] = {"model": summed["model"]}
        new_state.update(algo.finalize_extra_sums(
            fl, global_state, {k: summed[k] for k in extra_keys}))
        if participation:
            metrics = {"local_loss": finish_masked_loss(summed)}
        else:
            metrics = {"local_loss": summed["loss"] / shard.n_shards}
        if telemetry is not None:
            metrics.update(telemetry.finish(summed["tele"]))
        return new_state, metrics

    return local_fn, finish_fn


def make_compressed_round_fn(bundle: ModelBundle, fl: FLConfig, mode: str,
                             uplink, downlink, *, impl="auto",
                             shard: Optional[ClientSharding] = None,
                             telemetry=None, participation=False,
                             controller=None):
    """A federated round with the wire path routed through codecs.

    Returns round_fn(global_state, client_batches, n_examples, lr,
    ef_state, down_mirror, key) -> (new_global_state, metrics,
    new_ef_state, new_down_mirror):

      1. downlink: the server broadcasts the *model update* against a
         mirror of what clients already hold — it transmits
         ``downlink.encode(model - mirror)`` and every client forms
         ``bcast = mirror + decode(payload)``, which becomes the next
         mirror.  Compressing the update (not the raw weights) is what
         makes sparse downlink codecs sound: a top-k broadcast of the
         weights themselves would hand clients a mostly-zero network,
         while the mirrored update stream converges to the model
         (EF21-style server compression).  The mirror gap itself carries
         every previously-dropped unit of mass, so the compressor is
         applied STATELESSLY here — adding an error-feedback residual on
         top would count dropped mass twice and the stream provably
         diverges (g_{r+1} = 2e_r - e_{r-1} on unselected coordinates).
      2. each client trains locally, forms its delta vs the broadcast, and
         uplinks ``uplink.encode(delta, ef)`` (error-feedback state is
         per-client, threaded via ``ef_state`` with leading client axis).
      3. the server decodes every payload and applies the aggregate to its
         FULL-PRECISION model: ``model + Σ w_i · decode(payload_i)`` —
         downlink codec error therefore never accumulates into the server
         state (clients see it through the mirror only).  Identical to
         FedAvg's weighted model average when both codecs are identity.

    The algorithm's extra state (FedFusion's fusion module) rides along
    uncompressed, exactly as before — its raw bytes stay accounted in
    ``CommLog``.

    Under ``shard`` (see module docstring) ``ef_state`` carries the EF
    rows of THIS shard's positional clients only; steps 1 and the
    server-side model update run replicated (their inputs are replicated
    and the aggregate arrives via psum, so every shard applies the exact
    same update), and the per-client rng keys are the positional slice of
    the reference loop's full split.

    Controller contract (``repro.control``): with ``controller`` set the
    round fn takes a trailing ``ctrl_state`` dict (scalar leaves riding
    the superstep scan carry) and returns ``new_ctrl`` as a 5th output.
    The incoming ``ctrl_state["level"]`` selects the rung every client of
    THIS round encodes at; ``controller.update`` then runs replicated on
    the psum-completed round metrics (traced scalars, identical on every
    shard) to pick the next round's level — zero host round-trips, zero
    extra collectives.  With ``controller=None`` every traced code path
    is byte-identical to before this axis existed.
    """
    if controller is not None and telemetry is None:
        raise ValueError("a controller needs telemetry for its decision "
                         "signals (the engine forces the required taps on)")
    algo = _algorithm(fl)
    extra_keys = algo.extra_state
    run_clients = _make_compressed_clients(bundle, fl, mode, uplink,
                                           downlink, impl=impl, shard=shard,
                                           telemetry=telemetry,
                                           controller=controller)

    @in_part(AGGREGATE)
    def _finish(global_state, summed, stacked_extras, weights):
        # apply the aggregate update to the FULL-PRECISION server model;
        # the aggregate of the client models themselves is bcast+Σw·Δ, but
        # folding the broadcast's codec error back into the server state
        # would compound it round over round.
        new_model = jax.tree.map(lambda g, d: g + d.astype(g.dtype),
                                 global_state["model"], summed["delta"])
        new_state: Dict[str, Any] = {"model": new_model}
        if mode == "client_parallel":
            new_state.update(algo.aggregate_extras(
                fl, global_state, stacked_extras, weights, shard=shard))
        else:
            new_state.update(algo.finalize_extra_sums(
                fl, global_state, {k: summed[k] for k in extra_keys}))
        return new_state

    if controller is not None:
        if participation:
            def round_fn(global_state, client_batches, n_examples, lr,
                         ef_state, down_mirror, key, pmask, pstale,
                         ctrl_state):
                weights = normalize_weights(n_examples, shard)
                wsums, stacked_extras, new_ef, losses, bcast, tele = \
                    run_clients(global_state, client_batches, weights, lr,
                                ef_state, down_mirror, key, n_examples,
                                pmask, pstale, level=ctrl_state["level"])
                lsums = masked_loss_sums(losses, pmask)
                if mode == "client_parallel":
                    summed = psum_tree(
                        {"delta": wsums["delta"], "tele": tele, **lsums},
                        shard)
                else:
                    summed = psum_tree({**wsums, "tele": tele, **lsums},
                                       shard)
                new_state = _finish(global_state, summed, stacked_extras,
                                    weights)
                metrics = {"local_loss": finish_masked_loss(summed)}
                metrics.update(telemetry.finish(summed["tele"]))
                with part(AGGREGATE):
                    new_ctrl = controller.update(ctrl_state, metrics)
                return new_state, metrics, new_ef, bcast, new_ctrl
        else:
            def round_fn(global_state, client_batches, n_examples, lr,
                         ef_state, down_mirror, key, ctrl_state):
                weights = normalize_weights(n_examples, shard)
                wsums, stacked_extras, new_ef, losses, bcast, tele = \
                    run_clients(global_state, client_batches, weights, lr,
                                ef_state, down_mirror, key, n_examples,
                                level=ctrl_state["level"])
                if mode == "client_parallel":
                    summed = psum_tree(
                        {"delta": wsums["delta"], "tele": tele}, shard)
                else:
                    summed = psum_tree({**wsums, "tele": tele}, shard)
                new_state = _finish(global_state, summed, stacked_extras,
                                    weights)
                metrics = {"local_loss": mean_over_clients(losses, shard)}
                metrics.update(telemetry.finish(summed["tele"]))
                with part(AGGREGATE):
                    new_ctrl = controller.update(ctrl_state, metrics)
                return new_state, metrics, new_ef, bcast, new_ctrl

        return round_fn

    if participation:
        def round_fn(global_state, client_batches, n_examples, lr,
                     ef_state, down_mirror, key, pmask, pstale):
            weights = normalize_weights(n_examples, shard)
            wsums, stacked_extras, new_ef, losses, bcast, tele = \
                run_clients(global_state, client_batches, weights, lr,
                            ef_state, down_mirror, key, n_examples, pmask,
                            pstale)
            lsums = masked_loss_sums(losses, pmask)
            if mode == "client_parallel":
                summed = psum_tree(
                    {"delta": wsums["delta"], "tele": tele, **lsums}, shard)
            else:
                summed = psum_tree({**wsums, "tele": tele, **lsums}, shard)
            new_state = _finish(global_state, summed, stacked_extras,
                                weights)
            metrics = {"local_loss": finish_masked_loss(summed)}
            if telemetry is not None:
                metrics.update(telemetry.finish(summed["tele"]))
            return new_state, metrics, new_ef, bcast

        return round_fn

    def round_fn(global_state, client_batches, n_examples, lr, ef_state,
                 down_mirror, key):
        weights = normalize_weights(n_examples, shard)
        wsums, stacked_extras, new_ef, losses, bcast, tele = run_clients(
            global_state, client_batches, weights, lr, ef_state,
            down_mirror, key, n_examples)
        if mode == "client_parallel":
            # tele rides the delta-sum psum: same single collective
            summed = psum_tree({"delta": wsums["delta"], "tele": tele},
                               shard)
        else:
            summed = psum_tree({**wsums, "tele": tele}, shard)
        new_state = _finish(global_state, summed, stacked_extras, weights)
        metrics = {"local_loss": mean_over_clients(losses, shard)}
        if telemetry is not None:
            metrics.update(telemetry.finish(summed["tele"]))
        return new_state, metrics, new_ef, bcast

    return round_fn


def _make_compressed_clients(bundle: ModelBundle, fl: FLConfig, mode: str,
                             uplink, downlink, *, impl="auto",
                             shard: Optional[ClientSharding] = None,
                             telemetry=None, controller=None):
    """Shared client-side computation of one codec-routed round.

    Returns ``run_clients(global_state, client_batches, weights, lr,
    ef_state, down_mirror, key, n_examples) -> (wsums, stacked_extras,
    new_ef, losses, bcast, tele)``: ``wsums`` holds this shard's
    psum-pending weighted sums ``{"delta": tree, **extras}``,
    ``stacked_extras`` the per-client extras (client_parallel only),
    ``new_ef`` the positional clients' fresh EF rows, ``bcast`` the
    mirror-based downlink result (the clients' next mirror) and ``tele``
    this shard's telemetry tap sums (``{}`` when ``telemetry=None`` — the
    code path is then byte-identical to the untapped one).

    ``pmask``/``pstale`` (participation; ``None`` when the axis is off):
    a masked client's encoded payload never reaches the server (its
    weight is zero), so its EF update is rolled back — ``new_ef`` keeps
    the client's *incoming* residual bit for bit, exactly what the
    reference semantics of "this client never uplinked" require.  Both
    arrays also feed the telemetry tap contexts.

    ``level`` (a traced int32 scalar, ``None`` when no controller is on)
    selects the uplink codec's effective ladder rung for EVERY client of
    this round — it is a closure capture, not a vmapped operand, so all
    clients encode at the same level and the codec's capacity-shaped
    payload keeps the wire shapes static.  With ``level=None`` the encode
    traces exactly the pre-ladder program.
    """
    if mode not in ("client_parallel", "client_sequential"):
        raise ValueError(f"unknown fl mode {mode!r}")
    algo = _algorithm(fl)
    trainer = make_local_trainer(bundle, fl, impl=impl)
    extra_keys = algo.extra_state

    def run_clients(global_state, client_batches, weights, lr, ef_state,
                    down_mirror, key, n_examples=None, pmask=None,
                    pstale=None, level=None):
        n_clients = weights.shape[0]
        with part(CODEC):
            kd, ku = jax.random.split(key)
            down_update = jax.tree.map(lambda m, w: m - w,
                                       global_state["model"], down_mirror)
            down_payload, _ = downlink.encode(
                down_update, downlink.init_state(),   # stateless: see above
                kd if downlink.uses_key else None)
            bcast = jax.tree.map(lambda w, d: w + d.astype(w.dtype),
                                 down_mirror, downlink.decode(down_payload))
            client_keys = _local_client_keys(ku, n_clients, shard)
            eff_bytes = (None if level is None or controller is None
                         else jnp.take(controller.spec.bytes_table(), level))
        gx = algo.extra_from_state(global_state)

        def client_step(batches, ef, ck, nex=None, m=None, st=None):
            trainable, loss = trainer(bcast, gx, batches, lr)
            with part(CODEC):
                delta = jax.tree.map(lambda a, b: a - b, trainable["model"],
                                     bcast)
                payload, new_ef = uplink.encode(
                    delta, ef, ck if uplink.uses_key else None, level=level)
                decoded = uplink.decode(payload)
                if m is not None:
                    # dropped / late client: its payload never uplinked,
                    # so the residual it would have cleared stays local
                    new_ef = jax.tree.map(
                        lambda n, o: jnp.where(m > 0, n, o), new_ef, ef)
            out = {"delta": decoded, "ef": new_ef, "loss": loss}
            for k in extra_keys:
                out[k] = trainable[k]
            if telemetry is not None:
                out["tele"] = telemetry.client_sums(ClientTapCtx(
                    n_examples=nex, loss=loss, global_model=bcast,
                    delta=delta, decoded=decoded, ef=new_ef,
                    pmask=m, staleness=st, level=level,
                    eff_bytes=eff_bytes))
            return out

        if mode == "client_parallel":
            if pmask is None:
                if telemetry is None:
                    outs = jax.vmap(client_step)(client_batches, ef_state,
                                                 client_keys)
                    tele = {}
                else:
                    outs = jax.vmap(client_step)(client_batches, ef_state,
                                                 client_keys, n_examples)
                    tele = _sum_clients(outs["tele"])
            else:
                if telemetry is None:
                    outs = jax.vmap(
                        lambda b, e, k, m: client_step(b, e, k, m=m))(
                            client_batches, ef_state, client_keys, pmask)
                    tele = {}
                else:
                    outs = jax.vmap(client_step)(
                        client_batches, ef_state, client_keys, n_examples,
                        pmask, pstale)
                    tele = _sum_clients(outs["tele"])
            wsums = {"delta": _weighted_sums(outs["delta"], weights)}
            for k in extra_keys:
                wsums[k] = _weighted_sums(outs[k], weights)
            return (wsums, {k: outs[k] for k in extra_keys}, outs["ef"],
                    outs["loss"], bcast, tele)

        acc0 = {"delta": zeros_like_tree(global_state["model"])}
        for k in extra_keys:
            acc0[k] = zeros_like_tree(global_state[k])
        acc_keys = tuple(acc0)

        if pmask is None:
            if telemetry is None:
                def body(acc, xs):
                    batches, w, ef, ck = xs
                    out = client_step(batches, ef, ck)
                    acc = {k: running_update(acc[k], out[k], w)
                           for k in acc}
                    return acc, (out["ef"], out["loss"])

                acc, (new_ef, losses) = jax.lax.scan(
                    body, acc0,
                    (client_batches, weights, ef_state, client_keys))
                return acc, None, new_ef, losses, bcast, {}

            def body(acc, xs):
                batches, w, ef, ck, nex = xs
                out = client_step(batches, ef, ck, nex)
                acc = {k: running_update(acc[k], out[k], w)
                       for k in acc_keys}
                return acc, (out["ef"], out["loss"], out["tele"])

            acc, (new_ef, losses, tele_c) = jax.lax.scan(
                body, acc0, (client_batches, weights, ef_state, client_keys,
                             n_examples))
            return acc, None, new_ef, losses, bcast, _sum_clients(tele_c)

        if telemetry is None:
            def body(acc, xs):
                batches, w, ef, ck, m = xs
                out = client_step(batches, ef, ck, m=m)
                acc = {k: running_update(acc[k], out[k], w) for k in acc}
                return acc, (out["ef"], out["loss"])

            acc, (new_ef, losses) = jax.lax.scan(
                body, acc0, (client_batches, weights, ef_state, client_keys,
                             pmask))
            return acc, None, new_ef, losses, bcast, {}

        def body(acc, xs):
            batches, w, ef, ck, nex, m, st = xs
            out = client_step(batches, ef, ck, nex, m, st)
            acc = {k: running_update(acc[k], out[k], w) for k in acc_keys}
            return acc, (out["ef"], out["loss"], out["tele"])

        acc, (new_ef, losses, tele_c) = jax.lax.scan(
            body, acc0, (client_batches, weights, ef_state, client_keys,
                         n_examples, pmask, pstale))
        return acc, None, new_ef, losses, bcast, _sum_clients(tele_c)

    return run_clients


def make_compressed_round_parts(bundle: ModelBundle, fl: FLConfig,
                                mode: str, uplink, downlink, *, impl="auto",
                                shard: ClientSharding, telemetry=None,
                                participation=False, controller=None):
    """Deferred-psum split of :func:`make_compressed_round_fn`.

    Returns ``(local_fn, finish_fn)`` for the fused-collective superstep:

    ``local_fn(global_state, client_batches, total, n_examples, lr,
    ef_state, down_mirror, key) -> (contribs, aux)`` — ``contribs``
    ``{"delta": tree, **extras, "loss": scalar}`` are this shard's
    psum-pending sums; ``aux`` carries ``new_ef`` (positional clients'
    fresh EF rows, routed through the fused exchange by the superstep)
    and ``bcast`` (the next downlink mirror).  ``total`` is the round's
    psum-completed example count, pipelined one collective ahead.

    ``finish_fn(global_state, summed) -> (new_state, metrics)`` applies
    the psum-completed aggregate delta to the full-precision server model
    and closes extras through ``finalize_extra_sums`` (see
    :func:`make_round_parts` for why that stays bitwise).

    With ``controller`` set (``repro.control``): ``local_fn`` takes a
    trailing ``ctrl_state`` whose ``level`` selects the round's encode
    rung (pre-psum, shard-local), and ``finish_fn(global_state, summed,
    ctrl_state) -> (new_state, metrics, new_ctrl)`` runs the controller's
    decision rule on the psum-completed metrics (post-psum, replicated).
    The split adds NOTHING to the fused psum beyond the controller tap's
    two f32 lanes — the round stays exactly one collective.
    """
    if controller is not None and telemetry is None:
        raise ValueError("a controller needs telemetry for its decision "
                         "signals (the engine forces the required taps on)")
    algo = _algorithm(fl)
    extra_keys = algo.extra_state
    _check_extra_keys(extra_keys)
    run_clients = _make_compressed_clients(bundle, fl, mode, uplink,
                                           downlink, impl=impl, shard=shard,
                                           telemetry=telemetry,
                                           controller=controller)

    if controller is not None:
        if participation:
            def local_fn(global_state, client_batches, total, n_examples,
                         lr, ef_state, down_mirror, key, pmask, pstale,
                         ctrl_state):
                weights = weights_over_total(n_examples, total)
                wsums, _, new_ef, losses, bcast, tele = run_clients(
                    global_state, client_batches, weights, lr, ef_state,
                    down_mirror, key, n_examples, pmask, pstale,
                    level=ctrl_state["level"])
                contribs = {**wsums, **masked_loss_sums(losses, pmask),
                            "tele": tele}
                return contribs, {"new_ef": new_ef, "bcast": bcast}
        else:
            def local_fn(global_state, client_batches, total, n_examples,
                         lr, ef_state, down_mirror, key, ctrl_state):
                weights = weights_over_total(n_examples, total)
                wsums, _, new_ef, losses, bcast, tele = run_clients(
                    global_state, client_batches, weights, lr, ef_state,
                    down_mirror, key, n_examples,
                    level=ctrl_state["level"])
                contribs = {**wsums, "loss": mean_over_clients(losses),
                            "tele": tele}
                return contribs, {"new_ef": new_ef, "bcast": bcast}

        @in_part(AGGREGATE)
        def finish_fn(global_state, summed, ctrl_state):
            new_model = jax.tree.map(lambda g, d: g + d.astype(g.dtype),
                                     global_state["model"], summed["delta"])
            new_state: Dict[str, Any] = {"model": new_model}
            new_state.update(algo.finalize_extra_sums(
                fl, global_state, {k: summed[k] for k in extra_keys}))
            if participation:
                metrics = {"local_loss": finish_masked_loss(summed)}
            else:
                metrics = {"local_loss": summed["loss"] / shard.n_shards}
            metrics.update(telemetry.finish(summed["tele"]))
            new_ctrl = controller.update(ctrl_state, metrics)
            return new_state, metrics, new_ctrl

        return local_fn, finish_fn

    if participation:
        def local_fn(global_state, client_batches, total, n_examples, lr,
                     ef_state, down_mirror, key, pmask, pstale):
            weights = weights_over_total(n_examples, total)
            wsums, _, new_ef, losses, bcast, tele = run_clients(
                global_state, client_batches, weights, lr, ef_state,
                down_mirror, key, n_examples, pmask, pstale)
            contribs = {**wsums, **masked_loss_sums(losses, pmask),
                        "tele": tele}
            return contribs, {"new_ef": new_ef, "bcast": bcast}
    else:
        def local_fn(global_state, client_batches, total, n_examples, lr,
                     ef_state, down_mirror, key):
            weights = weights_over_total(n_examples, total)
            wsums, _, new_ef, losses, bcast, tele = run_clients(
                global_state, client_batches, weights, lr, ef_state,
                down_mirror, key, n_examples)
            contribs = {**wsums, "loss": mean_over_clients(losses),
                        "tele": tele}
            return contribs, {"new_ef": new_ef, "bcast": bcast}

    @in_part(AGGREGATE)
    def finish_fn(global_state, summed):
        new_model = jax.tree.map(lambda g, d: g + d.astype(g.dtype),
                                 global_state["model"], summed["delta"])
        new_state: Dict[str, Any] = {"model": new_model}
        new_state.update(algo.finalize_extra_sums(
            fl, global_state, {k: summed[k] for k in extra_keys}))
        if participation:
            metrics = {"local_loss": finish_masked_loss(summed)}
        else:
            metrics = {"local_loss": summed["loss"] / shard.n_shards}
        if telemetry is not None:
            metrics.update(telemetry.finish(summed["tele"]))
        return new_state, metrics

    return local_fn, finish_fn


def init_global_state(bundle: ModelBundle, fl: FLConfig, key):
    """Server line 1: initialise the global model (+ the algorithm's
    extra state — FedFusion's fusion module)."""
    algo = _algorithm(fl)
    k1, k2 = jax.random.split(key)
    state: Dict[str, Any] = {"model": bundle.init(k1)}
    state.update(algo.init_extra_state(bundle, fl, k2))
    return state
