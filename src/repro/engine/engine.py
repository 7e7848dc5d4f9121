"""Device-resident federated training engine (the server loop, replaced).

``run_federated_engine`` drives training as a sequence of jitted K-round
supersteps instead of one Python-dispatched round at a time:

* chunk schedule — the round range is cut at eval / checkpoint boundaries
  (host-visible state is only needed there) and otherwise into
  ``superstep_rounds``-sized chunks; when evaluation happens every round
  it is folded into the scan so the chunk size survives;
  ``superstep_rounds="auto"`` picks the chunk size from measured dispatch
  overhead (see :func:`_auto_chunk_rounds`);
* buffers — ``global_state`` (and for compressed runs the full-federation
  EF tree + broadcast mirror) are donated into every superstep call, and
  so are the staged chunk arrays (batches/sizes/lrs/cids), so steady-state
  chunks mutate device buffers in place and staging never leaks buffers;
* host pipeline — a prefetch thread stages the next chunk's client sample,
  batches and lr slice to device while the current chunk trains
  (``HostPrefetcher``, re-filling a ``StagingPool`` of pinned host
  buffers), and metrics come back through ``MetricsPump`` futures, so the
  host blocks only at checkpoint boundaries and at the end of the run;
* eval overlap — at an eval boundary the evaluator is dispatched on a
  device-side SNAPSHOT of the post-chunk state (``jnp.copy`` under jit),
  taken before that state is donated into the next chunk: chunk r+1
  starts while eval(r) runs, and the ``MetricsPump`` merges the eval
  future into the chunk's last round when it resolves (metrics therefore
  lag the training front by up to one chunk — same contract as every
  other engine metric);
* mesh — with ``mesh`` whose client axes (``pod``/``data``) multiply to
  S > 1, the superstep runs under ``shard_map`` (``repro.engine.sharded``):
  the chunk's client axis is split positionally over the S shards, the
  full-federation EF table is row-sharded by client id in the resident
  scratch-row layout (``[(N_loc+1)*S, ...]``, in-place per-round scatter;
  ``ef.npz`` stays the compact format), the compressed round's traffic is
  ONE packed psum (``fused_collective=True``, the default — EF exchange,
  aggregate and pipelined weight totals ride a single flat buffer;
  ``False`` keeps the bitwise-equal three-collective oracle), and
  evaluation splits the padded test batch over the shards with a
  masked-sum psum (``sharded_eval=True``; ``False`` evaluates
  replicated).  The results are allclose (not bitwise: aggregation order
  changes) to the single-device engine; ``mesh=None`` or S == 1 keeps the
  exact single-device program;
* equivalence — the rng streams (data sampling on the host, per-round
  ``fold_in`` on device) and the per-round math are exactly those of the
  preserved reference loop (``repro.fl.server.run_federated_reference``);
  at chunk size 1 the single-device final model is bitwise-identical to
  it;
* EF store — ``ef_store="device"`` keeps the dense ``[N, n]`` table (the
  bitwise oracle); ``"host"`` swaps in the cohort-paged store
  (``repro.engine.efstore``): only a ``[K*C, n]`` page of the sampled
  cohort's rows ever touches the device, staged one chunk ahead through
  the prefetch pipeline and written back asynchronously at chunk
  boundaries, with a device-side patch closing the one-chunk overlap
  window — device memory for EF becomes O(C·n), independent of the
  federation size, and the paged run stays bitwise-equal to the dense
  one.  ``"auto"`` (default) flips to the host store when the projected
  dense table exceeds ``_EF_STORE_AUTO_BYTES``.  ``ef.npz`` keeps the
  compact ``[N, n]`` format either way, so checkpoints resume across
  store layouts.

Semantics (checkpoint/resume layout, CommLog history, callback contract)
match the reference loop; a non-None ``callback`` forces one-round chunks
since it observes per-round state by contract.
"""
from __future__ import annotations

import contextlib
import copy
import functools
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import make_codec
from repro.configs.base import FLConfig
from repro.control import (LadderSpec, ladder_kind, ladder_values,
                           make_controller)
from repro.core.rounds import init_global_state
from repro.engine.efstore import EFPager, HostEFStore, plan_chunk_static
from repro.engine.evaljit import make_eval_fn, pad_eval_batch
from repro.engine.metrics import MetricsPump
from repro.engine.pipeline import HostPrefetcher, StagingPool
from repro.engine.sharded import (client_sharding, chunk_shardings,
                                  ef_table_sharding, eval_batch_sharding,
                                  make_sharded_eval, make_sharded_superstep)
from repro.engine.superstep import (donation_argnums,
                                    make_compressed_superstep,
                                    make_plain_superstep)
from repro.models.registry import ModelBundle
from repro.obs.runlog import as_runlog
from repro.obs.telemetry import Telemetry, make_telemetry
from repro.optim import exp_decay_per_round

# repro.fl.comm is imported lazily inside run_federated_engine:
# repro.fl.server imports this module, so the reverse edge would cycle.

_NON_METRIC_KEYS = frozenset(
    ("round", "bytes_up", "bytes_down", "bytes_up_ideal", "cum_bytes_up"))

# adaptive chunk sizing: pick K so the per-chunk dispatch overhead is at
# most this fraction of the chunk's device time, within [lo, hi]
_AUTO_TARGET_OVERHEAD = 0.05
_AUTO_BOUNDS = (8, 256)

# ef_store="auto": keep the dense device table while the projected
# [n_clients, n] EF footprint stays under this, page past it (1 GiB — a
# dense table that size is already >10% of small-accelerator HBM, while
# the paged path's per-chunk page is K*C rows regardless of N)
_EF_STORE_AUTO_BYTES = 1 << 30

# donate-safety without a host mirror: the broadcast mirror starts as a
# device-side COPY of the staged model (both are donated into the
# superstep; a shared buffer cannot be donated twice).  jnp.copy under
# jit preserves the input's sharding, and no host-side np.asarray
# duplicate of the model is retained for the lifetime of the run.
_device_copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))


@dataclass
class ServerResult:
    global_state: Dict
    comm: "repro.fl.comm.CommLog"  # noqa: F821 — lazy import, see above
    stats: Optional[Dict] = field(default=None, compare=False)


def chunk_schedule(start: int, rounds: int, chunk: int, *,
                   eval_every: Optional[int] = None,
                   ckpt_every: Optional[int] = None,
                   per_round: bool = False) -> List[Tuple[int, int]]:
    """Cut [start, rounds) into superstep chunks.

    Boundaries land exactly where the host must observe state: after round
    r when ``(r+1) % eval_every == 0`` (eval) or ``(r+1) % ckpt_every == 0``
    (checkpoint).  ``per_round=True`` (callback users) degenerates to
    one-round chunks.  Pass ``eval_every=None`` when evaluation is folded
    into the scan body — eval then imposes no boundary at all.
    """
    bounds = []
    r = start
    while r < rounds:
        if per_round:
            end = r + 1
        else:
            end = min(r + max(1, chunk), rounds)
            for every in (eval_every, ckpt_every):
                if every:
                    end = min(end, (r // every + 1) * every)
        bounds.append((r, end))
        r = end
    return bounds


def _calibration_source(data, seed: int):
    """A shallow clone of ``data`` with an independent rng stream.

    Adaptive chunk sizing times real supersteps on real-shaped chunks —
    but drawing those from ``data`` itself would advance the sampling rng
    and break bit-equivalence with the reference loop.  The clone shares
    the (read-only) client arrays and replaces only the stream.
    """
    clone = copy.copy(data)
    clone._rng = np.random.default_rng(seed ^ 0xCA11B)
    return clone


def _auto_chunk_rounds(get_step, build_calib, run_step, *,
                       target=_AUTO_TARGET_OVERHEAD, bounds=_AUTO_BOUNDS):
    """Pick the superstep chunk size from measured dispatch overhead.

    Times a compiled 1-round and an 8-round chunk on throwaway state
    (donation-safe: every measurement rebuilds its arguments).  With
    ``t_K ≈ overhead + K * per_round``, the two lengths identify both
    terms, and K is chosen so overhead amortizes below ``target`` of the
    chunk's device time.  The returned K is a throughput knob only —
    results are chunk-size-invariant (pinned by tests/test_engine.py).
    """
    def timed(n_rounds):
        step = get_step(n_rounds)
        jax.block_until_ready(run_step(step, build_calib(n_rounds)))
        t0 = time.perf_counter()
        jax.block_until_ready(run_step(step, build_calib(n_rounds)))
        return time.perf_counter() - t0

    t1, t8 = timed(1), timed(8)
    per_round = max((t8 - t1) / 7.0, 1e-7)
    overhead = max(t1 - per_round, 0.0)
    lo, hi = bounds
    return int(np.clip(round(overhead / (per_round * target)), lo, hi))


def _fit_prologue(engine):
    """Run ``engine`` inside the fit's ``fit.prologue`` span.

    The span opens at the engine's entry, with the clock that
    ``stats["fit_start_s"]`` reads; the engine closes it as its loop takes
    the first chunk (``prologue.close()``), and an error before then
    closes it here.  A runlog given as a path is opened, streamed and
    closed here."""
    @functools.wraps(engine)
    def run(bundle, fl, data, *, rounds, runlog=None, **options):
        t0 = time.perf_counter()
        owns_runlog = runlog is not None and not hasattr(runlog, "span")
        rl = as_runlog(runlog)
        try:
            with contextlib.ExitStack() as prologue:
                prologue.enter_context(rl.span("fit.prologue",
                                               rounds=rounds))
                return engine(bundle, fl, data, rounds=rounds, runlog=rl,
                              prologue=(prologue, t0), **options)
        finally:
            if owns_runlog:
                rl.close()
    return run


@_fit_prologue
def run_federated_engine(bundle: ModelBundle, fl: FLConfig, data, *,
                         rounds: int, seed: int = 0,
                         mode: str = "client_parallel",
                         eval_every: int = 1, eval_examples: int = 2048,
                         verbose: bool = False,
                         checkpoint_dir: Optional[str] = None,
                         checkpoint_every: int = 10,
                         callback: Optional[Callable] = None,
                         superstep_rounds=8, prefetch: bool = True,
                         impl: str = "auto", mesh=None,
                         overlap_eval: bool = True,
                         fused_collective: bool = True,
                         sharded_eval: bool = True,
                         ef_store: str = "auto",
                         telemetry=False, runlog=None,
                         halt_on_nonfinite: bool = False,
                         profile_dir: Optional[str] = None,
                         prologue=None) -> ServerResult:
    """Engine-backed server loop (see module docstring).

    Drop-in for the reference loop: same arguments, same ServerResult,
    same checkpoint layout and resume behaviour, plus ``superstep_rounds``
    (max rounds per jitted chunk, or ``"auto"`` to calibrate),
    ``prefetch`` (background host staging), ``impl`` (kernel dispatch for
    the EF gather/scatter and codecs), ``mesh`` (client-parallel
    ``shard_map`` execution when its pod/data axes multiply past 1),
    ``overlap_eval`` (snapshot-based eval dispatch; False reproduces the
    pre-overlap behaviour of evaluating the to-be-donated state),
    ``fused_collective`` (mesh only: ONE packed psum per round instead of
    the three-collective layout — bitwise-equal, False keeps the oracle),
    ``sharded_eval`` (mesh only: split the eval batch over the client
    shards with a masked-sum psum; False evaluates replicated) and
    ``ef_store`` (compressed only: ``"device"`` dense ``[N, n]`` EF
    table — the bitwise oracle; ``"host"`` the cohort-paged
    ``repro.engine.efstore`` store, O(C·n) device memory at any
    federation size, bitwise-equal to dense; ``"auto"`` pages once the
    projected dense table passes ``_EF_STORE_AUTO_BYTES``).

    Observability (``repro.obs``, all off by default):

    * ``telemetry`` — True (every applicable registered tap), a sequence
      of tap names, or a prebuilt :class:`repro.obs.telemetry.Telemetry`:
      on-device tap signals (``tele/...`` keys) ride the existing metrics
      stack and the round's existing psum — zero extra collectives, zero
      extra host syncs, and the trained model stays bitwise-equal to a
      telemetry-off run;
    * ``runlog`` — None | JSONL path | :class:`repro.obs.runlog.RunLog`:
      host span tracing (``fit.prologue`` with its ``ef.init``,
      ``chunk.dispatch``, ``eval.dispatch``, ``prefetch.stage`` with its
      ``.sample`` / ``.put`` / ``.fence`` parts, ``ef.page.*``,
      ``checkpoint.save``) plus counters and non-finite-metric warnings;
      a path given here is opened, streamed and closed by the engine.
      Whatever the sink, every span is also a host event of any
      ``jax.profiler`` session that is recording, and every chunk a
      ``superstep`` step (``StepTraceAnnotation``); the device ops carry
      the round's scopes (``repro.obs.scopes``);
    * ``profile_dir`` — start a ``jax.profiler`` trace into the directory
      for the whole run, compile included.

    ``prologue`` is the ``fit.prologue`` span and its start, opened by
    the wrapper (:func:`_fit_prologue`); callers never pass it.

    Robustness (both off by default — the defaults keep every traced code
    path byte-identical to the pre-robustness engine):

    * partial participation — ``fl.participation`` names a policy from
      ``repro.fl.participation`` (``full_sync`` / ``deadline`` /
      ``buffered_async``) and ``data`` may carry a
      :class:`repro.data.federated.ChaosConfig`.  When either deviates
      from the default, the engine samples the policy's (possibly
      over-provisioned) cohort, folds the host-decided mask / staleness
      weight / work fraction into the staged example weights (so dropped
      or late clients are zeroed INSIDE the existing one-psum — no shape
      changes, no extra collectives), carries masked clients' EF state
      forward untouched, and accounts per-round ``sim_time`` plus the
      partial uplink (``n_up``) in the CommLog;
    * ``halt_on_nonfinite`` — drain metrics at every chunk boundary and,
      on the first non-finite metric value, checkpoint the current state
      (if ``checkpoint_dir`` is set) and stop cleanly instead of training
      onward on garbage; ``stats["halted_at"]`` records the boundary.
    """
    from repro.checkpoint.io import (ef_disk_layout, insert_scratch_rows,
                                     load_tree, restore_server_state,
                                     save_server_state, save_tree)
    from repro.fl.comm import CommLog
    from repro.fl.participation import make_policy

    prologue, t_fit = prologue
    rl = runlog
    shard = client_sharding(mesh) if mesh is not None else None
    n_sampled = min(fl.clients_per_round, data.n_clients)

    # --- participation: who lands in each round, at what weight ------------
    # part_active=False (full_sync policy, no chaos) takes the exact
    # pre-participation code path everywhere: no extra round_chunk outputs,
    # no pmask/pstale superstep args, byte-identical traced programs.
    policy = make_policy(fl.participation)
    part_active = (getattr(data, "chaos", None) is not None
                   or policy.name != "full_sync")
    c_round = policy.cohort_size(n_sampled, fl) if part_active else n_sampled
    select_fn = None
    if part_active:
        def select_fn(draws):
            if draws is None:     # chaos off: everyone reports at t=1.0
                arrival = np.ones(c_round, np.float32)
                dropped = np.zeros(c_round, bool)
            else:
                arrival, dropped = draws.arrival, draws.dropped
            return policy.select(arrival, dropped, fl, n_sampled)

    if ef_store not in ("auto", "device", "host"):
        raise ValueError(f"ef_store={ef_store!r} not in "
                         "('auto', 'device', 'host')")
    if shard is not None:
        if c_round % shard.n_shards:
            raise ValueError(
                f"round cohort {c_round} (clients_per_round={n_sampled}, "
                f"policy {policy.name!r}) must divide over the mesh's "
                f"{shard.n_shards} client shards {shard.axes}")
        shard_batch, shard_repl = chunk_shardings(mesh)

    def _stage(x, sharded_like=False):
        if shard is None:
            return jax.device_put(x)
        return jax.device_put(x, shard_batch if sharded_like else shard_repl)

    key = jax.random.PRNGKey(seed)
    global_state = init_global_state(bundle, fl, key)
    start_round = 0
    if checkpoint_dir and os.path.exists(
            os.path.join(checkpoint_dir, "meta.json")):
        global_state, start_round = restore_server_state(checkpoint_dir,
                                                         global_state)
        # replay the consumed sampling stream (and, with chaos on, the
        # fault-schedule draws) so resumed rounds draw the exact
        # clients/batches/faults an uninterrupted run would have
        data.skip_round_sampling(start_round, c_round,
                                 fl.local_steps, fl.local_batch)
    global_state = jax.tree.map(lambda x: _stage(jnp.asarray(x)),
                                global_state)
    lr_at = exp_decay_per_round(fl.lr, fl.lr_decay)
    comm = CommLog().bind_sizes(global_state)

    # --- wire codecs: EF store (dense device table | cohort-paged) + mirror
    compressed = fl.compressed
    # adaptive compression controller (repro.control): "static" is the
    # bitwise oracle — controller stays None, no ladder is bound, no ctrl
    # state enters any carry, and every traced program is byte-identical
    # to the pre-controller engine.
    ctrl_active = compressed and fl.controller != "static"
    controller = ctrl_spec = ctrl_state = None
    wire_up = wire_down = None
    ef_all = down_mirror = round_key = None
    uplink = downlink = None
    ef_path = None
    ef_paged = False
    pager = None
    if compressed:
        uplink = make_codec(fl.uplink_codec, topk_frac=fl.topk_frac,
                            quant_bits=fl.quant_bits, impl=impl)
        downlink = make_codec(fl.downlink_codec, topk_frac=fl.topk_frac,
                              quant_bits=fl.quant_bits, impl=impl)
        uplink.bind(global_state["model"])
        downlink.bind(global_state["model"])
        wire_up = uplink.wire_bytes()
        wire_down = downlink.wire_bytes()
        if ctrl_active:
            # bind the ladder at the codec's capacity (= the configured
            # static level, enforced by ladder_values); the traced level
            # scalar masks the payload down to the effective rung
            ladder = ladder_values(fl)
            uplink.set_ladder(ladder)
            ctrl_spec = LadderSpec(kind=ladder_kind(fl.uplink_codec),
                                   values=ladder,
                                   bytes_up=uplink.level_bytes())
            controller = make_controller(fl.controller).setup(ctrl_spec, fl)
        ef_template = uplink.init_state()
        store = HostEFStore(ef_template)
        if store.n_leaves == 0:
            ef_paged = False   # stateless uplink (e.g. int8): nothing to page
        elif ef_store == "auto":
            ef_paged = (data.n_clients * store.row_nbytes()
                        > _EF_STORE_AUTO_BYTES)
        else:
            ef_paged = ef_store == "host"
        if shard is not None and not ef_paged \
                and data.n_clients % shard.n_shards:
            raise ValueError(
                f"n_clients={data.n_clients} must divide over the mesh's "
                f"{shard.n_shards} client shards (row-sharded EF table); "
                "ef_store='host' lifts the constraint")
        ef_path = (os.path.join(checkpoint_dir, "ef.npz")
                   if checkpoint_dir else None)
        resume_ef = bool(start_round and ef_path
                         and os.path.exists(ef_path))
        if shard is not None:
            ef_sh = ef_table_sharding(mesh)
        with rl.span("ef.init", paged=ef_paged, resume=resume_ef):
            if ef_paged:
                pager = EFPager(store, mesh=mesh, impl=impl, runlog=rl)
                if resume_ef:
                    # ef.npz is always the compact [n_clients, ...] layout;
                    # the store keeps only the non-zero rows of it
                    ef_dense = jax.tree.map(
                        lambda z: np.zeros((data.n_clients,) + z.shape,
                                           np.dtype(z.dtype)), ef_template)
                    ef_dense, down_host = load_tree(
                        ef_path, (ef_dense, global_state["model"]))
                    store.from_dense(ef_dense)
                    down_mirror = jax.tree.map(
                        lambda z: _stage(jnp.asarray(z)), down_host)
                else:
                    down_mirror = _device_copy(global_state["model"])
            else:
                ef_all = jax.tree.map(
                    lambda z: np.zeros((data.n_clients,) + z.shape,
                                       np.dtype(z.dtype)), ef_template)
                if resume_ef:
                    # ef.npz is always the compact [n_clients, ...] layout
                    ef_all, down_host = load_tree(
                        ef_path, (ef_all, global_state["model"]))
                    down_mirror = jax.tree.map(
                        lambda z: _stage(jnp.asarray(z)), down_host)
                else:
                    down_mirror = _device_copy(global_state["model"])
                if shard is not None:
                    # resident scratch-row layout: one permanent write-sink
                    # row per shard block, so the per-round scatter is in place
                    ef_all = insert_scratch_rows(ef_all, shard.n_shards)
                ef_all = jax.tree.map(
                    lambda z: (jax.device_put(z, ef_sh) if shard is not None
                               else jnp.asarray(z)), ef_all)
        round_key = jax.random.fold_in(key, 0x636f6d70)  # "comp"

    # --- observability: telemetry taps + host span tracing ----------------
    # tele=None keeps every traced code path byte-identical to the
    # pre-observability engine (the bitwise contract tests/test_obs.py pins)
    tele = None
    if telemetry or ctrl_active:
        if isinstance(telemetry, Telemetry):
            tele = telemetry
        else:
            # a controller's decision signals ride telemetry: force its
            # required taps (plus the schedule-exporting "controller" tap)
            # into the selection even when the user left telemetry off
            tap_names = (None if telemetry is True
                         else tuple(telemetry) if telemetry else ())
            if ctrl_active and tap_names is not None:
                tap_names = tuple(dict.fromkeys(
                    tap_names + tuple(controller.requires_taps)
                    + ("controller",)))
            tele = make_telemetry(
                "compressed" if compressed else "plain",
                n_clients=c_round,
                n_shards=shard.n_shards if shard is not None else 1,
                available=frozenset(
                    (("ef",) if compressed and uplink.stateful else ())
                    + (("pmask", "staleness") if part_active else ())
                    + (("level", "eff_bytes") if ctrl_active else ())),
                taps=tap_names)
        if ctrl_active:
            have = {t.name for t in tele.taps} if tele is not None else set()
            missing = [n for n in controller.requires_taps
                       if n not in have]
            if missing:
                raise ValueError(
                    f"controller {fl.controller!r} needs telemetry taps "
                    f"{missing}, unavailable for uplink codec "
                    f"{fl.uplink_codec!r} (e.g. the 'ef' tap needs a "
                    "stateful error-feedback uplink)")

    # controller state: staged replicated scalars; ctrl.npz sits next to
    # ef.npz so interrupt+resume replays the schedule bitwise
    ctrl_path = (os.path.join(checkpoint_dir, "ctrl.npz")
                 if checkpoint_dir else None)
    if ctrl_active:
        ctrl_host = jax.tree.map(np.asarray, controller.init_state())
        if start_round and ctrl_path and os.path.exists(ctrl_path):
            ctrl_host = load_tree(ctrl_path, ctrl_host)
        ctrl_state = jax.tree.map(lambda x: _stage(jnp.asarray(x)),
                                  ctrl_host)

    def save_ef():
        """ef.npz keeps the compact [n_clients, ...] layout, whatever the
        live backing (dense, sharded-resident, or paged store)."""
        if ef_paged:
            pager.flush()   # every submitted write-back is in the store
            ef_src = store
        else:
            ef_src = ef_all
        ef_disk = ef_disk_layout(
            ef_src, n_shards=shard.n_shards if shard is not None else 1,
            n_clients=data.n_clients)
        save_tree(ef_path, (ef_disk, down_mirror), runlog=rl)
        if ctrl_active:
            save_tree(ctrl_path, ctrl_state, runlog=rl)

    # --- fixed-shape evaluation -------------------------------------------
    # on a mesh the eval batch splits positionally over the client shards
    # and the masked metric sums cross one psum (S× less eval compute per
    # device — the paper's workload evaluates every round);
    # sharded_eval=False keeps the replicated-evaluator oracle.
    eval_shard = shard if (shard is not None and sharded_eval) else None
    test_batch = test_mask = None
    eval_fn = jit_eval = snap = None
    eval_in_scan = False
    if eval_every:
        test_batch, test_mask = pad_eval_batch(
            data.test_batch(), eval_examples,
            sharding=(eval_batch_sharding(mesh) if eval_shard is not None
                      else shard_repl if shard is not None else None),
            shard=eval_shard)
        eval_fn = make_eval_fn(bundle, fl, shard=eval_shard, impl=impl)
        eval_in_scan = eval_every == 1 and callback is None
        if not eval_in_scan:
            jit_eval = jax.jit(make_sharded_eval(eval_fn, mesh)
                               if eval_shard is not None else eval_fn)
        # eval overlap: the evaluator reads a device-side copy, never the
        # buffers the next chunk is about to consume by donation
        snap = (jax.jit(lambda t: jax.tree.map(jnp.copy, t))
                if (jit_eval is not None and overlap_eval) else None)

    # --- chunk staging -----------------------------------------------------
    # pinned-buffer reuse is an accelerator optimization: there device_put
    # is a real host->device DMA and block_until_ready fences it.  The CPU
    # backend may alias or lazily read the numpy buffer past that fence
    # (the "device" IS the host), so reuse would corrupt staged chunks —
    # CPU stages into fresh arrays, exactly the pre-pool behaviour.
    pool = StagingPool() if jax.default_backend() != "cpu" else None
    # seconds the fit's chunks spent in each part of their staging (the
    # children of prefetch.stage; calibration chunks are not counted)
    stage_s = {"sample": 0.0, "put": 0.0, "fence": 0.0}

    @contextlib.contextmanager
    def staging(part_name, r0, r1, counted):
        t0 = time.perf_counter()
        with rl.span("prefetch.stage." + part_name, r0=r0, r1=r1):
            yield
        if counted:
            stage_s[part_name] += time.perf_counter() - t0

    def build_chunk(r0, r1, src=None, staging_pool=None):
        counted = src is None
        with staging("sample", r0, r1, counted):
            out = (src or data).round_chunk(
                r1 - r0, c_round, fl.local_steps, fl.local_batch,
                pool=staging_pool, participation=select_fn)
            if select_fn is not None:
                cids, batches, sizes, part = out
                # the whole participation outcome is weight-borne:
                # dropped / late clients are zeroed (mask), staleness-
                # discounted (weight) and truncation-scaled (work) HERE,
                # on the host, so the staged example weights drive the
                # unchanged normalized weighted mean — the fused one-psum
                # never learns masking exists.  pmask/pstale only reach
                # the round fns for EF preservation, the masked loss
                # lanes and telemetry.
                sizes = sizes * part["mask"] * part["weight"] * part["work"]
            else:
                cids, batches, sizes, part = out + (None,)
            if compressed and ef_paged:
                # the superstep addresses EF rows by VIRTUAL cid — a slot
                # in the chunk's [K*C, ...] page.  Real training chunks
                # gather the page from the store (ordered after the
                # write-backs they depend on); calibration chunks get a
                # throwaway zero page and never touch store or pager.
                if src is None:
                    plan, page = pager.stage(cids, pool=staging_pool)
                else:
                    plan = plan_chunk_static(
                        cids, shard.n_shards if shard is not None else 1)
                    page = jax.tree_util.tree_unflatten(
                        store._treedef, pager.zero_page(plan))
        with staging("put", r0, r1, counted):
            staged = {
                "batches": {k: _stage(v, sharded_like=True)
                            for k, v in batches.items()},
                "sizes": _stage(sizes, sharded_like=True),
                # one vectorized schedule op, not K scalar dispatches —
                # the elementwise pow gives the same float32 values as
                # the reference loop's per-round lr_at(r)
                "lrs": lr_at(jnp.arange(r0, r1)),
            }
            if compressed:   # only the compressed superstep consumes these
                if ef_paged:
                    staged["cids"] = _stage(plan.vcids)
                    staged["ef_page"] = jax.tree.map(
                        lambda z: (jax.device_put(z, ef_sh)
                                   if shard is not None else jnp.asarray(z)),
                        page)
                    staged["ef_plan"] = plan
                else:
                    staged["cids"] = _stage(cids)
                staged["ridx"] = _stage(np.arange(r0, r1, dtype=np.int32))
            if part is not None:
                staged["pmask"] = _stage(part["mask"], sharded_like=True)
                staged["pstale"] = _stage(part["staleness"],
                                          sharded_like=True)
                # host-only accounting: simulated round wall-clock and the
                # partial uplink count ride the MetricsPump alongside the
                # device fetch — no device round-trip involved
                staged["host"] = {
                    "metrics": {"sim_time": part["round_time"],
                                "arrived": part["n_arrived"].astype(
                                    np.float32)},
                    "n_up": part["n_arrived"],
                }
        if staging_pool is not None:
            # free the pool's host buffers for the next chunk: the wait
            # lands on the PREFETCH thread, never the dispatch thread
            # (ef_plan is host metadata, not an array)
            with staging("fence", r0, r1, counted):
                jax.block_until_ready(
                    {k: v for k, v in staged.items() if k != "ef_plan"})
        return staged

    # --- jitted supersteps, cached per chunk length -----------------------
    steps: Dict[int, Callable] = {}

    def get_step(n_rounds):
        if n_rounds not in steps:
            in_scan = eval_fn if eval_in_scan else None
            if shard is not None:
                fn = make_sharded_superstep(
                    bundle, fl, mode, n_rounds, mesh, uplink=uplink,
                    downlink=downlink, eval_fn=in_scan, impl=impl,
                    fused_collective=fused_collective,
                    eval_sharded=eval_shard is not None, telemetry=tele,
                    participation=part_active, controller=controller)
            elif compressed:
                fn = make_compressed_superstep(
                    bundle, fl, mode, n_rounds, uplink, downlink,
                    eval_fn=in_scan, impl=impl, telemetry=tele,
                    participation=part_active, controller=controller)
            else:
                fn = make_plain_superstep(bundle, fl, mode, n_rounds,
                                          eval_fn=in_scan, impl=impl,
                                          telemetry=tele,
                                          participation=part_active)
            # donate the carried state and the lr slice: the buffers an
            # output can take over (see donation_argnums)
            donate = donation_argnums(
                compressed=compressed, participation=part_active,
                controller=ctrl_active)
            steps[n_rounds] = jax.jit(fn, donate_argnums=donate)
        return steps[n_rounds]

    test_args = (test_batch, test_mask) if eval_in_scan else ()

    def run_step(step, staged, state=None, ef=None, mirror=None, ctrl=None):
        """Dispatch one superstep on (state, staged); None -> throwaway
        zero trees (calibration — the real carries must not be donated)."""
        state = jax.tree.map(jnp.zeros_like, global_state) \
            if state is None else state
        part_args = ((staged["pmask"], staged["pstale"])
                     if part_active else ())
        if compressed:
            if ef is None:   # device-native zeros: donation-safe anywhere
                ef = jax.tree.map(jnp.zeros_like,
                                  staged["ef_page"] if ef_paged else ef_all)
            ctrl_args = ()
            if ctrl_active:
                ctrl_args = (jax.tree.map(jnp.zeros_like, ctrl_state)
                             if ctrl is None else ctrl,)
            mirror = jax.tree.map(jnp.zeros_like, down_mirror) \
                if mirror is None else mirror
            return step(state, ef, mirror, staged["batches"],
                        staged["sizes"], staged["lrs"], staged["cids"],
                        staged["ridx"], round_key, *part_args, *ctrl_args,
                        *test_args)
        return step(state, staged["batches"], staged["sizes"],
                    staged["lrs"], *part_args, *test_args)

    # --- chunk size: fixed or calibrated ----------------------------------
    chunk_rounds = superstep_rounds
    if superstep_rounds == "auto":
        calib = _calibration_source(data, seed)
        chunk_rounds = _auto_chunk_rounds(
            get_step, lambda n: build_chunk(0, n, src=calib), run_step)
        if verbose:
            print(f"engine: auto chunk size -> {chunk_rounds} rounds")

    # --- schedule + prefetch pipeline -------------------------------------
    schedule = chunk_schedule(
        start_round, rounds, chunk_rounds,
        eval_every=None if eval_in_scan else eval_every,
        ckpt_every=checkpoint_every if checkpoint_dir else None,
        per_round=callback is not None)

    prefetcher = HostPrefetcher(
        lambda r0, r1: build_chunk(r0, r1, staging_pool=pool),
        schedule, enabled=prefetch, runlog=rl)

    ctrl_schedule = None
    if ctrl_active:
        # per-round CommLog accounting: the level metric indexes these
        # host-side tables, so effective bytes replace the capacity
        # wire_up in every round record (schema v2, repro.fl.comm)
        eff_key = ("eff_topk_frac" if ctrl_spec.kind == "topk_frac"
                   else "eff_quant_bits")
        ctrl_schedule = {
            "bytes": [float(b) for b in ctrl_spec.bytes_up],
            "effective": [
                {"level": i,
                 eff_key: (float(v) if ctrl_spec.kind == "topk_frac"
                           else int(v))}
                for i, v in enumerate(ctrl_spec.values)],
        }
    pump = MetricsPump(comm, c_round, wire_up=wire_up,
                       wire_down=wire_down,
                       n_down=(data.n_clients
                               if fl.downlink_codec != "identity" else None),
                       verbose=verbose, runlog=rl,
                       schedule=ctrl_schedule)

    rl.event("run.start", rounds=rounds, start_round=start_round,
             chunk_rounds=chunk_rounds, compressed=compressed,
             client_shards=shard.n_shards if shard is not None else 1,
             telemetry=tele is not None,
             participation=policy.name if part_active else None,
             controller=fl.controller if ctrl_active else None,
             ef_store=("host" if ef_paged else "device") if compressed
                      else None)
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    halted_at = None
    fit_start_s = None
    try:
        # the pump context drains into the CommLog on a clean exit and
        # ABORTS (cancel + non-blocking shutdown) when unwinding an
        # exception — a mid-run error no longer leaks the worker thread
        with pump:
            for ci, (r0, r1, staged) in enumerate(prefetcher):
                if ci == 0:
                    prologue.close()
                with jax.profiler.StepTraceAnnotation("superstep",
                                                      step_num=ci):
                    with rl.span("chunk.dispatch", r0=r0, r1=r1,
                                 compile=(r1 - r0) not in steps):
                        step = get_step(r1 - r0)
                        if compressed and ef_paged:
                            # device patch closes the one-chunk write-back
                            # window, then the page rides the superstep in
                            # ef_all's place; the output page goes back to
                            # the store off-thread
                            ef_page = pager.patch(staged["ef_plan"],
                                                  staged["ef_page"])
                            out = run_step(step, staged, global_state,
                                           ef_page, down_mirror, ctrl_state)
                            if ctrl_active:
                                (global_state, mstack, ef_out, down_mirror,
                                 ctrl_state) = out
                            else:
                                (global_state, mstack, ef_out,
                                 down_mirror) = out
                            pager.complete(staged["ef_plan"], ef_out)
                        elif compressed:
                            out = run_step(step, staged, global_state,
                                           ef_all, down_mirror, ctrl_state)
                            if ctrl_active:
                                (global_state, mstack, ef_all, down_mirror,
                                 ctrl_state) = out
                            else:
                                (global_state, mstack, ef_all,
                                 down_mirror) = out
                        else:
                            global_state, mstack = run_step(step, staged,
                                                            global_state)
                    if fit_start_s is None:
                        fit_start_s = time.perf_counter() - t_fit
                    eval_metrics = None
                    if jit_eval is not None and eval_every \
                            and r1 % eval_every == 0:
                        with rl.span("eval.dispatch", round=r1,
                                     overlap=snap is not None):
                            eval_state = snap(global_state) \
                                if snap is not None else global_state
                            eval_metrics = jit_eval(eval_state, test_batch,
                                                    test_mask)
                pump.submit(mstack, eval_metrics,
                            host=staged.get("host"))
                if callback is not None:    # per-round chunks by contract
                    pump.drain()
                    metrics = {k: v for k, v in comm.history[-1].items()
                               if k not in _NON_METRIC_KEYS}
                    callback(r0, global_state, metrics)
                if halt_on_nonfinite:
                    # the drain costs the metrics overlap — that is the
                    # documented price of the option (off by default)
                    pump.drain()
                    if pump.nonfinite_round is not None:
                        rl.event("run.halt", reason="metrics.nonfinite",
                                 round=pump.nonfinite_round, boundary=r1)
                        if checkpoint_dir:
                            with rl.span("checkpoint.save", round=r1,
                                         halt=True):
                                save_server_state(
                                    checkpoint_dir, global_state, r1,
                                    extra={"algorithm": fl.algorithm,
                                           "halted": True}, runlog=rl)
                                if compressed:
                                    save_ef()
                        halted_at = r1
                        break
                if checkpoint_dir and r1 % checkpoint_every == 0:
                    with rl.span("checkpoint.save", round=r1):
                        save_server_state(checkpoint_dir, global_state, r1,
                                          extra={"algorithm": fl.algorithm},
                                          runlog=rl)
                        if compressed:
                            save_ef()
    finally:
        if pager is not None:
            # wakes a prefetch thread blocked in pager.stage (it aborts
            # through the prefetcher's error path) and drains pending
            # write-backs, so the final save below reads a settled store
            pager.close()
        prefetcher.close()
        if profile_dir:
            jax.profiler.stop_trace()

    if checkpoint_dir and halted_at is None:
        with rl.span("checkpoint.save", round=rounds, final=True):
            save_server_state(checkpoint_dir, global_state, rounds,
                              extra={"algorithm": fl.algorithm},
                              runlog=rl)
            if compressed:
                save_ef()
    stats = {
        "chunk_rounds": chunk_rounds,
        "client_shards": shard.n_shards if shard is not None else 1,
        "fused_collective": bool(shard is not None and fused_collective),
        "sharded_eval": eval_fn is not None and eval_shard is not None,
        "eval_overlap": snap is not None,
        "host_wait_s": round(prefetcher.wait_s, 4),
        "metrics_wait_s": round(pump.wait_s, 4),
        "stage_sample_s": round(stage_s["sample"], 4),
        "stage_put_s": round(stage_s["put"], 4),
        "stage_fence_s": round(stage_s["fence"], 4),
        "fit_start_s": (round(fit_start_s, 4) if fit_start_s is not None
                        else None),
        "telemetry": tele is not None,
        "participation": policy.name if part_active else None,
        "round_cohort": c_round,
        "halted_at": halted_at,
        "controller": fl.controller if ctrl_active else None,
        "ladder": list(ctrl_spec.values) if ctrl_active else None,
        "ef_store": ("host" if ef_paged else "device") if compressed
                    else None,
    }
    if ef_paged:
        # O(C·n) headline: peak device bytes the EF pages ever occupied —
        # a function of chunk size and cohort, never of n_clients
        stats["ef_page_bytes"] = pager.page_rows_max * store.row_nbytes()
        stats["ef_store_rows"] = store.n_rows
        stats["ef_stall_s"] = round(pager.stall_s, 4)
        rl.counter("ef.page.hits", store.hits)
        rl.counter("ef.page.misses", store.misses)
        rl.counter("ef.page.writeback_rows", store.writeback_rows)
        rl.counter("ef.page.patched_rows", pager.patched_rows)
        rl.counter("ef.page.stall_s", stats["ef_stall_s"])
    rl.counter("prefetch.wait_s", stats["host_wait_s"])
    rl.counter("metrics.wait_s", stats["metrics_wait_s"])
    rl.event("run.end", rounds=rounds)
    if rl.path:
        stats["runlog"] = rl.path
    return ServerResult(global_state=global_state, comm=comm, stats=stats)
