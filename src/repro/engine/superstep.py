"""Jitted K-round supersteps: ``lax.scan`` over the federated round fn.

One superstep call turns K pre-staged rounds entirely on device:

* client sampling arrives as a pre-sampled ``cids [K, C]`` array (drawn on
  the host by the prefetch pipeline with the exact rng stream of the
  one-round-at-a-time loop);
* the lr schedule arrives as a ``lrs [K]`` array;
* the compressed path's full-federation error-feedback tree and broadcast
  mirror ride the scan carry: each round gathers the sampled clients' EF
  rows (``ops.ef_gather``), runs the compressed round fn, and scatters the
  new residuals back with a fused in-place row scatter (``ops.ef_scatter``
  — ``.at[cids].set`` under donation on the jnp path, an aliased Pallas
  kernel on TPU).  The per-round device->host->device NumPy round-trip of
  the old server loop is gone;
* per-round metrics come back stacked ``[K]`` so the host never has to
  block mid-chunk, and when evaluation happens every round (the paper's
  accuracy-per-round curves) the fixed-shape evaluator is folded straight
  into the scan body.

``K == 1`` bypasses ``lax.scan`` and applies the round body to the leading
slice directly, so a chunk-size-1 engine run compiles the same per-round
computation as the reference loop — that is what makes the K=1 final model
bitwise-equal to the pre-engine loop (the equivalence contract
``tests/test_engine.py`` pins down).

Sharded mode (``shard`` = a :class:`repro.core.aggregate.ClientSharding`):
the superstep becomes a ``shard_map`` BODY (see ``repro.engine.sharded``).
Batches/sizes then carry only this shard's positional client slice, the
EF table argument is this shard's row block PLUS ONE RESIDENT SCRATCH ROW
(``[N_loc+1, ...]`` — rows ``[pos*N_loc, (pos+1)*N_loc)`` of the full
federation sharded by client id, row ``N_loc`` a write sink for non-owned
scatter rows), and ``cids`` stays the FULL round sample (replicated —
ownership of an EF row is decided by cid, not by which shard trains the
client).  The scratch row lives in the table layout permanently
(:func:`repro.launch.sharding.ef_table_sharding` allocates it at staging;
``repro.checkpoint.io`` drops it at save and re-appends it on restore),
so the per-round scatter is a single in-place ``ops.ef_scatter`` on the
donated block instead of a concatenate + slice pair copying the whole
block twice per round.  With ``shard=None`` nothing changes.

Collectives (sharded only):

* ``fused=False`` — the three-collective oracle: FedAvg aggregation psum
  inside the round fn, plus one compact ``[C, ...]`` psum exchange per
  direction for the EF rows (``ef_gather_exchange`` /
  ``ef_scatter_exchange``);
* ``fused=True`` (the engine default) — ONE psum per round: the round's
  local contribution sums (delta / extras / loss via
  ``repro.core.rounds.make_*_round_parts``), the EF scatter placement,
  the NEXT round's EF gather contributions and the next round's example
  -count total are packed into one flat buffer and exchanged with a
  single ``psum`` (:func:`repro.core.aggregate.fused_psum`; pack offsets
  are trace-time statics, unpack is static slices).  Quantities a round
  needs BEFORE training — its gathered EF rows and its weight
  total — are pipelined one collective ahead: they ride the previous
  round's psum (a per-chunk prologue psum seeds round 0), which is
  possible because ``cids``/``sizes`` are pre-staged inputs and a
  just-trained row's fresh value is known to the shard that trained it
  before the scatter lands.  Every packed element equals its standalone
  -psum value bitwise, so fused and unfused rounds agree bit for bit.

Cohort-paged EF (``ef_store="host"``, see ``repro.engine.efstore``): the
superstep itself is layout-agnostic — every row access goes through
``cids`` and ``table.shape[0]``.  The engine exploits that by passing a
chunk-local PAGE as ``ef_all`` (``[K*C, ...]`` unsharded, or per-shard
blocks ``[P_loc+1, ...]`` with the same resident scratch row) and
page-relative VIRTUAL ids as ``cids``: the ownership math below
(``n_loc = table.shape[0] - 1``; ``owned = (cids >= lo) & (cids < lo +
n_loc)``) and the cross-round match in ``_ef_gather_next_contrib`` only
require that equal ids mean the same row and distinct ids mean distinct
rows within the chunk — which the paging plan guarantees (one slot per
distinct client per chunk).  Nothing in this module special-cases paging.

The caller jits the returned function; donate ``global_state`` (and for
the compressed path ``ef_all`` + ``mirror``) so steady-state chunks update
those buffers in place instead of reallocating them every call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.aggregate import fused_psum
from repro.core.rounds import (init_global_state, make_compressed_round_fn,
                               make_compressed_round_parts, make_round_fn,
                               make_round_parts)
from repro.kernels import ops
from repro.obs.scopes import AGGREGATE, CODEC, in_part, part


def donation_argnums(*, compressed, participation=False, controller=False):
    """The engine's ``donate_argnums`` for a superstep signature.

    One source of truth shared by ``repro.engine.engine`` (which jits the
    supersteps) and ``repro.analysis`` (whose donation pass verifies the
    donated buffers are actually aliased in the compiled executable).
    Only buffers an output can take over are donated, on every backend:

    * the carried device state — ``global_state`` (and for the compressed
      path ``ef_all`` + ``mirror``, plus the controller scalars) — is
      consumed exactly once per chunk and comes back with the same shapes;
    * the lr slice ``[K]`` f32 is taken over by a ``[K]`` metric row;
    * the staged chunk arrays (batches / sizes / cids / round_idx and the
      participation mask/staleness) are NOT donated: no output has their
      shapes, so XLA could never reuse them and would only warn.
    """
    if compressed:
        donate = (0, 1, 2, 5)
        if controller:
            donate = donate + ((11,) if participation else (9,))
        return donate
    return (0, 3)


def abstract_superstep_args(bundle, fl, n_rounds, *, cohort, uplink=None,
                            ef_rows=None, participation=False,
                            controller=None, input_shape=None):
    """ShapeDtypeStruct argument tuple matching a superstep's signature.

    The invariant analyzer (``repro.analysis``) and the jaxpr-level tests
    trace supersteps abstractly; this helper is the single place the
    argument layout is spelled out, so signature changes break one
    builder instead of five hand-rolled copies.

    ``cohort`` is the round's client count C (already policy-expanded
    for partial participation); ``ef_rows`` is the leading row count of
    the EF table argument — ``n_clients`` dense unsharded,
    ``(n_loc + 1) * n_shards`` resident sharded, ``K*C`` /
    ``(K*C + 1) * n_shards`` for the cohort-paged layouts — required
    exactly when ``uplink`` is a bound codec.  ``controller`` is a
    set-up :class:`repro.control.Controller` (its ``init_state()``
    shapes the ctrl arg).  ``input_shape`` defaults to the bundle
    config's ``input_shape``.
    """
    K, C = n_rounds, cohort
    S, B = fl.local_steps, fl.local_batch
    if input_shape is None:
        input_shape = tuple(bundle.config.input_shape)
    state = jax.eval_shape(lambda k: init_global_state(bundle, fl, k),
                           jax.random.PRNGKey(0))
    batches = {"x": jax.ShapeDtypeStruct((K, C, S, B) + input_shape,
                                         jnp.float32),
               "y": jax.ShapeDtypeStruct((K, C, S, B), jnp.int32)}
    sizes = jax.ShapeDtypeStruct((K, C), jnp.float32)
    lrs = jax.ShapeDtypeStruct((K,), jnp.float32)
    part = ((jax.ShapeDtypeStruct((K, C), jnp.float32),
             jax.ShapeDtypeStruct((K, C), jnp.float32))
            if participation else ())
    ctrl = ((jax.eval_shape(controller.init_state),)
            if controller is not None else ())
    if uplink is None:
        return (state, batches, sizes, lrs) + part
    if ef_rows is None:
        raise ValueError("abstract_superstep_args needs ef_rows for a "
                         "compressed superstep (the EF table's leading "
                         "row count)")
    ef = jax.tree.map(
        lambda z: jax.ShapeDtypeStruct((ef_rows,) + z.shape, z.dtype),
        jax.eval_shape(uplink.init_state))
    cids = jax.ShapeDtypeStruct((K, C), jnp.int32)
    ridx = jax.ShapeDtypeStruct((K,), jnp.int32)
    round_key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return (state, ef, state["model"], batches, sizes, lrs, cids, ridx,
            round_key) + part + ctrl


def _stack1(tree):
    """Metrics of a single round -> the [1]-stacked layout scan returns."""
    return jax.tree.map(lambda v: jnp.asarray(v)[None], tree)


def _size_total(n_examples):
    """This shard's term of a round's example-count total — the local half
    of ``normalize_weights`` (psum completes it, one round ahead)."""
    return jnp.sum(jnp.asarray(n_examples, jnp.float32))


def make_plain_superstep(bundle, fl, mode, n_rounds, *, eval_fn=None,
                         impl="auto", shard=None, fused=False,
                         telemetry=None, participation=False):
    """Uncompressed K-round superstep.

    Returns ``superstep(global_state, batches, sizes, lrs[, test_batch,
    test_mask]) -> (new_global_state, metrics stacked [K])`` with leading
    dims ``batches [K, C, steps, B, ...]``, ``sizes [K, C]``, ``lrs [K]``.
    ``eval_fn`` (traceable, from :func:`repro.engine.make_eval_fn`) folds
    per-round evaluation of the post-round state into the scan.  Under
    ``shard`` the batch/size client axis is this shard's slice and
    ``eval_fn`` must match how the test args are laid out (replicated, or
    positionally sharded for a shard-aware evaluator).  ``fused=True``
    (sharded only) runs the round's aggregation as ONE packed psum with
    the weight total pipelined one round ahead (see module docstring).

    ``participation=True`` inserts ``pmask [K, C]`` / ``pstale [K, C]``
    after ``lrs`` (this shard's positional slice under ``shard``, like
    sizes) and scans them through the participation-aware round fn; the
    sizes arriving here are already mask-and-staleness-weighted by the
    engine, so weight plumbing — including the fused pipelined total — is
    untouched.
    """
    if fused:
        if shard is None:
            raise ValueError("fused collectives require a shard "
                             "(fused=True is sharded-only)")
        return _make_fused_plain_superstep(bundle, fl, mode, n_rounds,
                                           eval_fn=eval_fn, impl=impl,
                                           shard=shard, telemetry=telemetry,
                                           participation=participation)
    round_fn = make_round_fn(bundle, fl, mode, impl=impl, shard=shard,
                             telemetry=telemetry,
                             participation=participation)

    def one_round(state, xs, test):
        state, metrics = round_fn(state, *xs)
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(state, test[0], test[1])}
        return state, metrics

    if participation:
        if n_rounds == 1:
            def superstep(global_state, batches, sizes, lrs, pmask, pstale,
                          *test):
                b0 = jax.tree.map(lambda a: a[0], batches)
                state, m = one_round(
                    global_state,
                    (b0, sizes[0], lrs[0], pmask[0], pstale[0]), test)
                return state, _stack1(m)
            return superstep

        def superstep(global_state, batches, sizes, lrs, pmask, pstale,
                      *test):
            def body(state, xs):
                return one_round(state, xs, test)

            return jax.lax.scan(body, global_state,
                                (batches, sizes, lrs, pmask, pstale))

        return superstep

    if n_rounds == 1:
        def superstep(global_state, batches, sizes, lrs, *test):
            b0 = jax.tree.map(lambda a: a[0], batches)
            state, m = one_round(global_state, (b0, sizes[0], lrs[0]), test)
            return state, _stack1(m)
        return superstep

    def superstep(global_state, batches, sizes, lrs, *test):
        def body(state, xs):
            return one_round(state, xs, test)

        return jax.lax.scan(body, global_state, (batches, sizes, lrs))

    return superstep


def _make_fused_plain_superstep(bundle, fl, mode, n_rounds, *, eval_fn,
                                impl, shard, telemetry=None,
                                participation=False):
    """One-psum-per-round uncompressed superstep (shard_map body)."""
    local_fn, finish_fn = make_round_parts(bundle, fl, mode, impl=impl,
                                           shard=shard, telemetry=telemetry,
                                           participation=participation)

    def one_round(state, total, b, n, lr, n_next, test, pm=None, ps=None):
        if participation:
            contribs = local_fn(state, b, total, n, lr, pm, ps)
        else:
            contribs = local_fn(state, b, total, n, lr)
        with part(AGGREGATE):
            summed = fused_psum({"round": contribs,
                                 "total": _size_total(n_next)}, shard)
        state, metrics = finish_fn(state, summed["round"])
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(state, test[0], test[1])}
        return state, summed["total"], metrics

    if participation:
        def superstep(global_state, batches, sizes, lrs, pmask, pstale,
                      *test):
            with part(AGGREGATE):
                total = fused_psum({"total": _size_total(sizes[0])},
                                   shard)["total"]
            if n_rounds == 1:
                b0 = jax.tree.map(lambda a: a[0], batches)
                state, _, m = one_round(global_state, total, b0, sizes[0],
                                        lrs[0], sizes[0], test,
                                        pmask[0], pstale[0])
                return state, _stack1(m)
            sizes_next = jnp.roll(sizes, -1, axis=0)

            def body(carry, xs):
                state, total = carry
                b, n, lr, n_next, pm, ps = xs
                state, total, m = one_round(state, total, b, n, lr, n_next,
                                            test, pm, ps)
                return (state, total), m

            (state, _), mstack = jax.lax.scan(
                body, (global_state, total),
                (batches, sizes, lrs, sizes_next, pmask, pstale))
            return state, mstack

        return superstep

    def superstep(global_state, batches, sizes, lrs, *test):
        # prologue: round 0's weight total (later rounds' ride the scan)
        with part(AGGREGATE):
            total = fused_psum({"total": _size_total(sizes[0])},
                               shard)["total"]
        if n_rounds == 1:
            b0 = jax.tree.map(lambda a: a[0], batches)
            state, _, m = one_round(global_state, total, b0, sizes[0],
                                    lrs[0], sizes[0], test)
            return state, _stack1(m)
        sizes_next = jnp.roll(sizes, -1, axis=0)

        def body(carry, xs):
            state, total = carry
            b, n, lr, n_next = xs
            state, total, m = one_round(state, total, b, n, lr, n_next,
                                        test)
            return (state, total), m

        (state, _), mstack = jax.lax.scan(
            body, (global_state, total), (batches, sizes, lrs, sizes_next))
        return state, mstack

    return superstep


# ---------------------------------------------------------------------------
# Row-sharded EF exchange (shard_map body helpers)
# ---------------------------------------------------------------------------
# The sharded EF table block is ALWAYS the resident scratch-row layout
# ``[N_loc+1, ...]``: row ``N_loc`` is a permanent write sink, so the
# exchanges below treat ``table.shape[0] - 1`` as the owned-row count.

def _ef_gather_contrib(table, cids, shard, *, impl="auto"):
    """This shard's masked term of the round's [C, ...] gather psum."""
    n_loc = table.shape[0] - 1
    lo = shard.position() * n_loc
    owned = (cids >= lo) & (cids < lo + n_loc)
    local_idx = jnp.clip(cids - lo, 0, n_loc - 1).astype(jnp.int32)
    rows = ops.ef_gather(table, local_idx, impl=impl)
    mask = owned.reshape((-1,) + (1,) * (rows.ndim - 1))
    return jnp.where(mask, rows, jnp.zeros_like(rows))


def ef_gather_exchange(table, cids, shard, *, impl="auto"):
    """Assemble the round's full [C, ...] EF rows from row-sharded blocks.

    ``table`` is this shard's LOCAL row block ``[N_loc+1, ...]`` of the
    federation table (shard ``s`` owns client ids ``[s*N_loc,
    (s+1)*N_loc)``; the trailing scratch row is never read); ``cids [C]``
    is the full round sample (replicated).  Each shard gathers the sampled
    rows it owns — a shard-local ``ops.ef_gather`` with clipped indices —
    masks the rest to zero, and one ``psum`` over the client axes gives
    every shard the complete [C, ...] matrix.  Rows are disjointly owned,
    so the sum is exact.
    """
    return jax.lax.psum(_ef_gather_contrib(table, cids, shard, impl=impl),
                        shard.axis_name)


def _ef_place_positional(new_rows, shard):
    """Place this shard's [C_loc, ...] rows at their positional offset in
    a zero [C, ...] buffer (the scatter exchange's psum operand)."""
    c_loc = new_rows.shape[0]
    full = jnp.zeros((c_loc * shard.n_shards,) + new_rows.shape[1:],
                     new_rows.dtype)
    return jax.lax.dynamic_update_slice_in_dim(
        full, new_rows, (shard.position() * c_loc).astype(jnp.int32),
        axis=0)


def _ef_scatter_local(table, cids, full, shard, *, impl="auto"):
    """Scatter the psum-completed [C, ...] rows this shard owns into its
    resident block, routing non-owned rows to the scratch row (``N_loc``)
    so the in-place ``ops.ef_scatter`` never sees a colliding index — a
    clipped index could alias a genuinely-owned row and ``.at[].set`` with
    duplicate indices keeps an arbitrary write."""
    n_loc = table.shape[0] - 1
    lo = shard.position() * n_loc
    owned = (cids >= lo) & (cids < lo + n_loc)
    safe_idx = jnp.where(owned, cids - lo, n_loc).astype(jnp.int32)
    return ops.ef_scatter(table, safe_idx, full, impl=impl)


def ef_scatter_exchange(table, cids, new_rows, shard, *, impl="auto"):
    """Write this shard's freshly-trained EF rows back to their owners.

    ``new_rows [C_loc, ...]`` are the residuals of this shard's POSITIONAL
    clients; their cids may be owned by any shard.  The rows are placed at
    their positional offset in a zero [C, ...] buffer, one ``psum``
    broadcasts the complete set, and each shard scatters the rows it owns
    into its resident ``[N_loc+1, ...]`` block IN PLACE (under donation) —
    the permanent scratch row absorbs non-owned rows, so no concatenate /
    slice copies the block.
    """
    full = jax.lax.psum(_ef_place_positional(new_rows, shard),
                        shard.axis_name)
    return _ef_scatter_local(table, cids, full, shard, impl=impl)


def _ef_gather_next_contrib(table, cids_prev, cids_next, new_rows, shard,
                            *, impl="auto"):
    """This shard's term of the NEXT round's gather psum, computable
    BEFORE the current round's scatter lands (the fused-path pipelining).

    For next-round position ``j`` with client ``c = cids_next[j]``:

    * ``c`` trained this round on THIS shard -> contribute the fresh row
      straight from ``new_rows`` (the post-scatter table value, known here
      first);
    * ``c`` trained on another shard -> contribute nothing (that shard
      has the fresh row);
    * ``c`` not trained this round -> the owner shard contributes its
      table row, which the pending scatter leaves untouched.

    Within-round cids are unique (``sample_clients`` asserts it), so
    exactly one shard contributes per row and the psum is exact — every
    summed row equals what ``ef_gather_exchange`` on the post-scatter
    table would produce.
    """
    n_loc = table.shape[0] - 1
    pos = shard.position()
    lo = pos * n_loc
    c_loc = new_rows.shape[0]
    prev_local = jax.lax.dynamic_slice_in_dim(
        cids_prev, (pos * c_loc).astype(jnp.int32), c_loc, axis=0)
    match = cids_next[:, None] == prev_local[None, :]        # [C, C_loc]
    trained_here = jnp.any(match, axis=1)
    local_pos = jnp.argmax(match, axis=1).astype(jnp.int32)
    from_train = jnp.take(new_rows, local_pos, axis=0)
    trained_any = jnp.any(cids_next[:, None] == cids_prev[None, :], axis=1)
    owned = (cids_next >= lo) & (cids_next < lo + n_loc)
    local_idx = jnp.clip(cids_next - lo, 0, n_loc - 1).astype(jnp.int32)
    from_table = ops.ef_gather(table, local_idx, impl=impl)
    mt = trained_here.reshape((-1,) + (1,) * (from_train.ndim - 1))
    mo = (owned & ~trained_any).reshape(
        (-1,) + (1,) * (from_table.ndim - 1))
    return jnp.where(mt, from_train,
                     jnp.where(mo, from_table, jnp.zeros_like(from_table)))


def _slice_positional(full_tree, shard, c_loc):
    """This shard's positional [C_loc, ...] block of full [C, ...] rows."""
    start = (shard.position() * c_loc).astype(jnp.int32)
    return jax.tree.map(
        lambda g: jax.lax.dynamic_slice_in_dim(g, start, c_loc, axis=0),
        full_tree)


def make_compressed_superstep(bundle, fl, mode, n_rounds, uplink, downlink,
                              *, eval_fn=None, impl="auto", shard=None,
                              fused=False, telemetry=None,
                              participation=False, controller=None):
    """Compressed (codec-routed) K-round superstep.

    Returns ``superstep(global_state, ef_all, mirror, batches, sizes, lrs,
    cids, round_idx, round_key[, test_batch, test_mask]) ->
    (new_global_state, metrics [K], new_ef_all, new_mirror)``.

    ``ef_all`` holds the FULL federation's per-client uplink EF residuals
    (leaves ``[n_clients, n]``) on device; ``cids [K, C]`` selects each
    round's rows.  ``round_idx [K]`` feeds ``fold_in(round_key, r)`` inside
    the scan, reproducing the reference loop's per-round key derivation
    bit for bit (fold_in is a pure function of the key data and r).

    Under ``shard``, ``ef_all`` is this shard's resident scratch-row block
    ``[N_loc+1, n]`` and the row movement goes through
    :func:`ef_gather_exchange` / :func:`ef_scatter_exchange` (three
    collectives per round) or, with ``fused=True``, one packed psum per
    round (see module docstring); ``cids`` stays the full round sample.

    ``participation=True`` inserts ``pmask [K, C]`` / ``pstale [K, C]``
    after ``round_key`` (this shard's positional slice under ``shard``).
    A masked client's EF row comes back equal to its incoming value (the
    round fn rolls the update back), so the unchanged scatter path writes
    the residual forward untouched.

    ``controller`` (``repro.control``) appends a ``ctrl_state`` argument
    after ``pmask``/``pstale`` (before the test args) and a 5th output:
    the controller's scalar state rides the scan carry exactly like the
    EF table and the mirror, so the level schedule advances across the
    whole chunk — and across chunks, since the engine threads the
    returned state into the next superstep call — without a single host
    round-trip.  With ``controller=None`` every traced code path is
    byte-identical to before this axis existed.
    """
    if fused:
        if shard is None:
            raise ValueError("fused collectives require a shard "
                             "(fused=True is sharded-only)")
        return _make_fused_compressed_superstep(
            bundle, fl, mode, n_rounds, uplink, downlink, eval_fn=eval_fn,
            impl=impl, shard=shard, telemetry=telemetry,
            participation=participation, controller=controller)
    round_fn = make_compressed_round_fn(bundle, fl, mode, uplink, downlink,
                                        impl=impl, shard=shard,
                                        telemetry=telemetry,
                                        participation=participation,
                                        controller=controller)

    @in_part(CODEC)
    def gather_rows(ef_all, cids, c_loc):
        if shard is None:
            return jax.tree.map(
                lambda t: ops.ef_gather(t, cids, impl=impl), ef_all)
        return _slice_positional(
            jax.tree.map(
                lambda t: ef_gather_exchange(t, cids, shard, impl=impl),
                ef_all),
            shard, c_loc)

    @in_part(CODEC)
    def scatter_rows(ef_all, cids, new_ef):
        if shard is None:
            return jax.tree.map(
                lambda t, rows: ops.ef_scatter(t, cids, rows, impl=impl),
                ef_all, new_ef)
        return jax.tree.map(
            lambda t, rows: ef_scatter_exchange(t, cids, rows, shard,
                                                impl=impl),
            ef_all, new_ef)

    def one_round(state, ef_all, mirror, b, n, lr, cids, r, round_key, test,
                  pm=None, ps=None):
        ef_round = gather_rows(ef_all, cids, n.shape[0])
        with part(CODEC):   # the round key feeds the codecs alone
            key_r = jax.random.fold_in(round_key, r)
        if participation:
            state, metrics, new_ef, mirror = round_fn(
                state, b, n, lr, ef_round, mirror, key_r, pm, ps)
        else:
            state, metrics, new_ef, mirror = round_fn(
                state, b, n, lr, ef_round, mirror, key_r)
        ef_all = scatter_rows(ef_all, cids, new_ef)
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(state, test[0], test[1])}
        return state, ef_all, mirror, metrics

    if controller is not None:
        def one_round_ctrl(state, ef_all, mirror, ctrl, b, n, lr, cids, r,
                           round_key, test, pm=None, ps=None):
            ef_round = gather_rows(ef_all, cids, n.shape[0])
            with part(CODEC):   # the round key feeds the codecs alone
                key_r = jax.random.fold_in(round_key, r)
            if participation:
                state, metrics, new_ef, mirror, ctrl = round_fn(
                    state, b, n, lr, ef_round, mirror, key_r, pm, ps, ctrl)
            else:
                state, metrics, new_ef, mirror, ctrl = round_fn(
                    state, b, n, lr, ef_round, mirror, key_r, ctrl)
            ef_all = scatter_rows(ef_all, cids, new_ef)
            if eval_fn is not None:
                metrics = {**metrics, **eval_fn(state, test[0], test[1])}
            return state, ef_all, mirror, ctrl, metrics

        if participation:
            if n_rounds == 1:
                def superstep(global_state, ef_all, mirror, batches, sizes,
                              lrs, cids, round_idx, round_key, pmask, pstale,
                              ctrl_state, *test):
                    b0 = jax.tree.map(lambda a: a[0], batches)
                    state, ef_all, mirror, ctrl, m = one_round_ctrl(
                        global_state, ef_all, mirror, ctrl_state, b0,
                        sizes[0], lrs[0], cids[0], round_idx[0], round_key,
                        test, pmask[0], pstale[0])
                    return state, _stack1(m), ef_all, mirror, ctrl
                return superstep

            def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                          cids, round_idx, round_key, pmask, pstale,
                          ctrl_state, *test):
                def body(carry, xs):
                    state, ef_all, mirror, ctrl = carry
                    b, n, lr, cid, r, pm, ps = xs
                    state, ef_all, mirror, ctrl, m = one_round_ctrl(
                        state, ef_all, mirror, ctrl, b, n, lr, cid, r,
                        round_key, test, pm, ps)
                    return (state, ef_all, mirror, ctrl), m

                (state, ef_all, mirror, ctrl), mstack = jax.lax.scan(
                    body, (global_state, ef_all, mirror, ctrl_state),
                    (batches, sizes, lrs, cids, round_idx, pmask, pstale))
                return state, mstack, ef_all, mirror, ctrl

            return superstep

        if n_rounds == 1:
            def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                          cids, round_idx, round_key, ctrl_state, *test):
                b0 = jax.tree.map(lambda a: a[0], batches)
                state, ef_all, mirror, ctrl, m = one_round_ctrl(
                    global_state, ef_all, mirror, ctrl_state, b0, sizes[0],
                    lrs[0], cids[0], round_idx[0], round_key, test)
                return state, _stack1(m), ef_all, mirror, ctrl
            return superstep

        def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                      cids, round_idx, round_key, ctrl_state, *test):
            def body(carry, xs):
                state, ef_all, mirror, ctrl = carry
                b, n, lr, cid, r = xs
                state, ef_all, mirror, ctrl, m = one_round_ctrl(
                    state, ef_all, mirror, ctrl, b, n, lr, cid, r,
                    round_key, test)
                return (state, ef_all, mirror, ctrl), m

            (state, ef_all, mirror, ctrl), mstack = jax.lax.scan(
                body, (global_state, ef_all, mirror, ctrl_state),
                (batches, sizes, lrs, cids, round_idx))
            return state, mstack, ef_all, mirror, ctrl

        return superstep

    if participation:
        if n_rounds == 1:
            def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                          cids, round_idx, round_key, pmask, pstale, *test):
                b0 = jax.tree.map(lambda a: a[0], batches)
                state, ef_all, mirror, m = one_round(
                    global_state, ef_all, mirror, b0, sizes[0], lrs[0],
                    cids[0], round_idx[0], round_key, test,
                    pmask[0], pstale[0])
                return state, _stack1(m), ef_all, mirror
            return superstep

        def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                      cids, round_idx, round_key, pmask, pstale, *test):
            def body(carry, xs):
                state, ef_all, mirror = carry
                b, n, lr, cid, r, pm, ps = xs
                state, ef_all, mirror, m = one_round(
                    state, ef_all, mirror, b, n, lr, cid, r, round_key,
                    test, pm, ps)
                return (state, ef_all, mirror), m

            (state, ef_all, mirror), mstack = jax.lax.scan(
                body, (global_state, ef_all, mirror),
                (batches, sizes, lrs, cids, round_idx, pmask, pstale))
            return state, mstack, ef_all, mirror

        return superstep

    if n_rounds == 1:
        def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                      cids, round_idx, round_key, *test):
            b0 = jax.tree.map(lambda a: a[0], batches)
            state, ef_all, mirror, m = one_round(
                global_state, ef_all, mirror, b0, sizes[0], lrs[0], cids[0],
                round_idx[0], round_key, test)
            return state, _stack1(m), ef_all, mirror
        return superstep

    def superstep(global_state, ef_all, mirror, batches, sizes, lrs, cids,
                  round_idx, round_key, *test):
        def body(carry, xs):
            state, ef_all, mirror = carry
            b, n, lr, cid, r = xs
            state, ef_all, mirror, m = one_round(
                state, ef_all, mirror, b, n, lr, cid, r, round_key, test)
            return (state, ef_all, mirror), m

        (state, ef_all, mirror), mstack = jax.lax.scan(
            body, (global_state, ef_all, mirror),
            (batches, sizes, lrs, cids, round_idx))
        return state, mstack, ef_all, mirror

    return superstep


def _make_fused_compressed_superstep(bundle, fl, mode, n_rounds, uplink,
                                     downlink, *, eval_fn, impl, shard,
                                     telemetry=None, participation=False,
                                     controller=None):
    """One-psum-per-round compressed superstep (shard_map body).

    Pipelining layout: a per-chunk prologue psum seeds round 0's gathered
    EF rows and weight total; thereafter round r's single psum carries its
    contribution sums, its scatter placement, round r+1's gather
    contributions and round r+1's weight total.  The last round's
    next-round slots are computed from rolled inputs and discarded —
    keeping the scan body uniform costs one dead [C, n] lane in the final
    psum of each chunk.

    Participation keeps this layout intact: masked clients are zeroed by
    the pre-weighted sizes (so the pipelined totals need no change), a
    masked client's ``new_ef`` equals its incoming row (the round fn
    rolls the update back), and the mask-weighted loss sums are two f32
    lanes in the same packed psum — still exactly ONE psum per round.
    """
    local_fn, finish_fn = make_compressed_round_parts(
        bundle, fl, mode, uplink, downlink, impl=impl, shard=shard,
        telemetry=telemetry, participation=participation,
        controller=controller)

    def round_psum(contribs, ef_all, new_ef, cid, cid_next, n_next):
        """The round's one psum: its contribution sums, the EF rows'
        scatter placement, round r+1's gather terms and weight total."""
        with part(CODEC):
            scat = jax.tree.map(
                lambda rows: _ef_place_positional(rows, shard), new_ef)
            gath = jax.tree.map(
                lambda t, rows: _ef_gather_next_contrib(
                    t, cid, cid_next, rows, shard, impl=impl),
                ef_all, new_ef)
        with part(AGGREGATE):
            return fused_psum({"round": contribs, "scat": scat,
                               "gath": gath, "total": _size_total(n_next)},
                              shard)

    @in_part(CODEC)
    def land_rows(ef_all, summed, cid, c_loc):
        """Scatter the owned rows of the psum into the resident block;
        slice round r+1's positional EF rows out of it."""
        ef_all = jax.tree.map(
            lambda t, full: _ef_scatter_local(t, cid, full, shard,
                                              impl=impl),
            ef_all, summed["scat"])
        return ef_all, _slice_positional(summed["gath"], shard, c_loc)

    def one_round(state, ef_all, mirror, ef_rows, total, b, n, lr, cid,
                  cid_next, n_next, r, round_key, test, pm=None, ps=None):
        with part(CODEC):   # the round key feeds the codecs alone
            key_r = jax.random.fold_in(round_key, r)
        if participation:
            contribs, aux = local_fn(state, b, total, n, lr, ef_rows,
                                     mirror, key_r, pm, ps)
        else:
            contribs, aux = local_fn(state, b, total, n, lr, ef_rows,
                                     mirror, key_r)
        summed = round_psum(contribs, ef_all, aux["new_ef"], cid, cid_next,
                            n_next)
        state, metrics = finish_fn(state, summed["round"])
        ef_all, ef_next = land_rows(ef_all, summed, cid, n.shape[0])
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(state, test[0], test[1])}
        return state, ef_all, aux["bcast"], ef_next, summed["total"], metrics

    def one_round_ctrl(state, ef_all, mirror, ef_rows, total, ctrl, b, n,
                       lr, cid, cid_next, n_next, r, round_key, test,
                       pm=None, ps=None):
        # Identical psum layout to one_round: the controller reads the
        # round's summed tap metrics AFTER the single collective and its
        # state transition is pure replicated scalar math, so adaptivity
        # adds zero collectives to the round.
        with part(CODEC):   # the round key feeds the codecs alone
            key_r = jax.random.fold_in(round_key, r)
        if participation:
            contribs, aux = local_fn(state, b, total, n, lr, ef_rows,
                                     mirror, key_r, pm, ps, ctrl)
        else:
            contribs, aux = local_fn(state, b, total, n, lr, ef_rows,
                                     mirror, key_r, ctrl)
        summed = round_psum(contribs, ef_all, aux["new_ef"], cid, cid_next,
                            n_next)
        state, metrics, ctrl = finish_fn(state, summed["round"], ctrl)
        ef_all, ef_next = land_rows(ef_all, summed, cid, n.shape[0])
        if eval_fn is not None:
            metrics = {**metrics, **eval_fn(state, test[0], test[1])}
        return (state, ef_all, aux["bcast"], ef_next, summed["total"],
                ctrl, metrics)

    def _prologue(ef_all, cids, sizes):
        # round 0's EF rows + weight total in one psum
        with part(CODEC):
            gather = jax.tree.map(
                lambda t: _ef_gather_contrib(t, cids[0], shard, impl=impl),
                ef_all)
        with part(AGGREGATE):
            seed = fused_psum({"gather": gather,
                               "total": _size_total(sizes[0])}, shard)
        with part(CODEC):
            rows = _slice_positional(seed["gather"], shard, sizes.shape[1])
        return rows, seed["total"]

    if controller is not None:
        if participation:
            def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                          cids, round_idx, round_key, pmask, pstale,
                          ctrl_state, *test):
                ef_rows, total = _prologue(ef_all, cids, sizes)
                if n_rounds == 1:
                    b0 = jax.tree.map(lambda a: a[0], batches)
                    state, ef_all, mirror, _, _, ctrl, m = one_round_ctrl(
                        global_state, ef_all, mirror, ef_rows, total,
                        ctrl_state, b0, sizes[0], lrs[0], cids[0], cids[0],
                        sizes[0], round_idx[0], round_key, test,
                        pmask[0], pstale[0])
                    return state, _stack1(m), ef_all, mirror, ctrl

                cids_next = jnp.roll(cids, -1, axis=0)
                sizes_next = jnp.roll(sizes, -1, axis=0)

                def body(carry, xs):
                    state, ef_all, mirror, ef_rows, total, ctrl = carry
                    b, n, lr, cid, cid_next, n_next, r, pm, ps = xs
                    (state, ef_all, mirror, ef_rows, total, ctrl,
                     m) = one_round_ctrl(
                        state, ef_all, mirror, ef_rows, total, ctrl, b, n,
                        lr, cid, cid_next, n_next, r, round_key, test,
                        pm, ps)
                    return (state, ef_all, mirror, ef_rows, total, ctrl), m

                (state, ef_all, mirror, _, _, ctrl), mstack = jax.lax.scan(
                    body,
                    (global_state, ef_all, mirror, ef_rows, total,
                     ctrl_state),
                    (batches, sizes, lrs, cids, cids_next, sizes_next,
                     round_idx, pmask, pstale))
                return state, mstack, ef_all, mirror, ctrl

            return superstep

        def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                      cids, round_idx, round_key, ctrl_state, *test):
            ef_rows, total = _prologue(ef_all, cids, sizes)
            if n_rounds == 1:
                b0 = jax.tree.map(lambda a: a[0], batches)
                state, ef_all, mirror, _, _, ctrl, m = one_round_ctrl(
                    global_state, ef_all, mirror, ef_rows, total,
                    ctrl_state, b0, sizes[0], lrs[0], cids[0], cids[0],
                    sizes[0], round_idx[0], round_key, test)
                return state, _stack1(m), ef_all, mirror, ctrl

            cids_next = jnp.roll(cids, -1, axis=0)
            sizes_next = jnp.roll(sizes, -1, axis=0)

            def body(carry, xs):
                state, ef_all, mirror, ef_rows, total, ctrl = carry
                b, n, lr, cid, cid_next, n_next, r = xs
                (state, ef_all, mirror, ef_rows, total, ctrl,
                 m) = one_round_ctrl(
                    state, ef_all, mirror, ef_rows, total, ctrl, b, n, lr,
                    cid, cid_next, n_next, r, round_key, test)
                return (state, ef_all, mirror, ef_rows, total, ctrl), m

            (state, ef_all, mirror, _, _, ctrl), mstack = jax.lax.scan(
                body,
                (global_state, ef_all, mirror, ef_rows, total, ctrl_state),
                (batches, sizes, lrs, cids, cids_next, sizes_next,
                 round_idx))
            return state, mstack, ef_all, mirror, ctrl

        return superstep

    if participation:
        def superstep(global_state, ef_all, mirror, batches, sizes, lrs,
                      cids, round_idx, round_key, pmask, pstale, *test):
            ef_rows, total = _prologue(ef_all, cids, sizes)
            if n_rounds == 1:
                b0 = jax.tree.map(lambda a: a[0], batches)
                state, ef_all, mirror, _, _, m = one_round(
                    global_state, ef_all, mirror, ef_rows, total, b0,
                    sizes[0], lrs[0], cids[0], cids[0], sizes[0],
                    round_idx[0], round_key, test, pmask[0], pstale[0])
                return state, _stack1(m), ef_all, mirror

            cids_next = jnp.roll(cids, -1, axis=0)
            sizes_next = jnp.roll(sizes, -1, axis=0)

            def body(carry, xs):
                state, ef_all, mirror, ef_rows, total = carry
                b, n, lr, cid, cid_next, n_next, r, pm, ps = xs
                state, ef_all, mirror, ef_rows, total, m = one_round(
                    state, ef_all, mirror, ef_rows, total, b, n, lr, cid,
                    cid_next, n_next, r, round_key, test, pm, ps)
                return (state, ef_all, mirror, ef_rows, total), m

            (state, ef_all, mirror, _, _), mstack = jax.lax.scan(
                body, (global_state, ef_all, mirror, ef_rows, total),
                (batches, sizes, lrs, cids, cids_next, sizes_next,
                 round_idx, pmask, pstale))
            return state, mstack, ef_all, mirror

        return superstep

    def superstep(global_state, ef_all, mirror, batches, sizes, lrs, cids,
                  round_idx, round_key, *test):
        ef_rows, total = _prologue(ef_all, cids, sizes)
        if n_rounds == 1:
            b0 = jax.tree.map(lambda a: a[0], batches)
            state, ef_all, mirror, _, _, m = one_round(
                global_state, ef_all, mirror, ef_rows, total, b0,
                sizes[0], lrs[0], cids[0], cids[0], sizes[0], round_idx[0],
                round_key, test)
            return state, _stack1(m), ef_all, mirror

        cids_next = jnp.roll(cids, -1, axis=0)
        sizes_next = jnp.roll(sizes, -1, axis=0)

        def body(carry, xs):
            state, ef_all, mirror, ef_rows, total = carry
            b, n, lr, cid, cid_next, n_next, r = xs
            state, ef_all, mirror, ef_rows, total, m = one_round(
                state, ef_all, mirror, ef_rows, total, b, n, lr, cid,
                cid_next, n_next, r, round_key, test)
            return (state, ef_all, mirror, ef_rows, total), m

        (state, ef_all, mirror, _, _), mstack = jax.lax.scan(
            body, (global_state, ef_all, mirror, ef_rows, total),
            (batches, sizes, lrs, cids, cids_next, sizes_next, round_idx))
        return state, mstack, ef_all, mirror

    return superstep
