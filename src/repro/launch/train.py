"""Pod-scale federated training launcher (pjit on a real device mesh).

Builds the same step as the dry-run (build_train_step) but on a mesh
factorized from the devices that actually exist — 1 CPU here, a v5e pod in
production — and runs real rounds with synthetic federated data.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --algorithm fedfusion --rounds 10 --scale tiny
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_CONFIGS, INPUT_SHAPES
from repro.configs.base import FLConfig, InputShape
from repro.core.rounds import init_global_state
from repro.fl.api import ALGORITHM_NAMES
from repro.data.partition import source_partition
from repro.data.synth import token_stream
from repro.launch import sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import fl_plan
from repro.launch.steps import build_train_step
from repro.models.registry import make_bundle
from repro.optim import exp_decay_per_round


def mesh_from_devices():
    """Factor the available devices into (data, model)."""
    n = len(jax.devices())
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"))


def run_engine(args, cfg, fl) -> None:
    """Drive the same workload through the client-parallel engine.

    Instead of the hand-rolled pjit round loop below, build a federated
    token dataset and hand it to a :class:`repro.fl.api.FederatedTrainer`
    on a mesh whose whole device count backs the CLIENT axis
    (``launch.mesh.make_engine_mesh``): the K-round superstep runs under
    ``shard_map``, clients split over ``data``, chunk staging/eval
    overlap/adaptive chunk sizing included.  On one device this
    degenerates to the single-device engine.
    """
    from repro.data.federated import ChaosConfig, FederatedDataset
    from repro.fl.api import (EngineOptions, EvalOptions, FederatedTrainer,
                              RunOptions)
    from repro.launch.mesh import client_axes, make_engine_mesh

    mesh = make_engine_mesh()
    shards = 1
    for a in client_axes(mesh):
        shards *= mesh.shape[a]
    # the sampled-client axis must split evenly over the mesh
    ladder = (tuple(float(v) for v in args.ladder.split(","))
              if args.ladder else ())
    if args.controller != "static" and args.uplink_codec == "identity":
        # adaptive compression needs something to adapt: default to the
        # top-k + error-feedback codec at the paper's keep fraction
        args.uplink_codec = "topk"
    fl = dataclasses.replace(
        fl, clients_per_round=max(fl.clients_per_round, shards)
        // shards * shards,
        participation=args.participation,
        over_provision=args.over_provision,
        buffer_k=args.buffer_k,
        staleness_alpha=args.staleness_alpha,
        uplink_codec=args.uplink_codec,
        topk_frac=args.topk_frac,
        controller=args.controller,
        ladder=ladder)
    # over-provisioned cohorts must still divide over the shards; size the
    # federation off the policy's cohort so sampling never starves
    from repro.fl.participation import make_policy
    c_round = make_policy(fl.participation).cohort_size(
        fl.clients_per_round, fl)
    c_round = -(-c_round // shards) * shards
    n_clients = 2 * max(fl.clients_per_round, c_round)
    bundle = make_bundle(cfg, jnp.float32)
    chaos = None
    if args.chaos:
        chaos = ChaosConfig(speed_sigma=args.chaos_speed_sigma,
                            jitter=args.chaos_jitter,
                            dropout=args.chaos_dropout,
                            truncation=args.chaos_truncation)

    toks, src = token_stream(
        max(n_clients * fl.local_batch * 8, 128), args.seq_len,
        vocab=cfg.vocab_size, n_sources=n_clients)
    test_toks, _ = token_stream(64, args.seq_len, vocab=cfg.vocab_size,
                                n_sources=n_clients, seed=1)
    data = FederatedDataset(source_partition(toks, src, n_clients),
                            {"tokens": test_toks}, seed=0, chaos=chaos)
    print(f"engine mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"clients/round={fl.clients_per_round} federation={n_clients}"
          + (f" participation={fl.participation}"
             if fl.participation != "full_sync" else "")
          + (" chaos=on" if chaos is not None else "")
          + (f" controller={fl.controller} uplink={fl.uplink_codec}"
             if fl.controller != "static" else ""))
    trainer = FederatedTrainer(bundle, fl, data, RunOptions(
        seed=0, verbose=True,
        eval=EvalOptions(every=max(args.rounds // 2, 1), examples=64),
        engine=EngineOptions(superstep_rounds="auto",
                             mesh=mesh if shards > 1 else None,
                             ef_store=args.ef_store,
                             telemetry=args.telemetry,
                             runlog=args.runlog,
                             halt_on_nonfinite=args.halt_on_nonfinite,
                             profile_dir=args.profile)))
    t0 = time.perf_counter()
    res = trainer.fit(args.rounds)
    dt = time.perf_counter() - t0
    print(f"done: {args.rounds} rounds in {dt:.1f}s "
          f"({args.rounds / dt:.2f} r/s)  stats={res.stats}")
    if args.telemetry and res.comm.history:
        last = res.comm.history[-1]
        tele = {k: v for k, v in last.items() if k.startswith("tele/")}
        if tele:
            print("telemetry (last round): " +
                  " ".join(f"{k}={v:.4g}" for k, v in sorted(tele.items())))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(ARCH_CONFIGS))
    ap.add_argument("--algorithm", default="fedavg",
                    choices=sorted(ALGORITHM_NAMES))
    ap.add_argument("--fusion-op", default="conv")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--scale", default="tiny", choices=("tiny", "full"))
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--engine", action="store_true",
                    help="run via the client-parallel shard_map engine "
                         "(repro.engine) instead of the pjit round loop")
    ap.add_argument("--ef-store", default="auto",
                    choices=("auto", "device", "host"),
                    help="engine only: EF residual backing — dense device "
                         "table, cohort-paged host store, or size-based "
                         "auto (paged runs are bitwise-equal)")
    ap.add_argument("--telemetry", action="store_true",
                    help="engine only: enable repro.obs on-device telemetry "
                         "taps (tele/... metrics; bitwise-invisible)")
    ap.add_argument("--runlog", default=None, metavar="PATH",
                    help="engine only: stream host span traces / events to "
                         "this JSONL file (repro.obs.RunLog)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="engine only: write a jax.profiler trace of the "
                         "whole run, compile included, into DIR (open it "
                         "in TensorBoard or Perfetto).  Device ops carry "
                         "the round's scopes (local_train, codec, "
                         "aggregate, eval) in their name stacks; the host "
                         "line holds one 'superstep' step per chunk and "
                         "the engine's spans (fit.prologue, ef.init, "
                         "prefetch.stage.sample/.put/.fence, "
                         "chunk.dispatch, eval.dispatch, ef.page.*, "
                         "checkpoint.save), as in any jax.profiler "
                         "session")
    ap.add_argument("--participation", default="full_sync",
                    help="engine only: round participation policy "
                         "(full_sync | deadline | buffered_async | any "
                         "registered name)")
    ap.add_argument("--over-provision", type=float, default=1.5,
                    help="deadline policy: cohort over-sampling factor")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="buffered_async policy: close the round at the "
                         "K-th arrival (0 -> clients_per_round // 2)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="buffered_async policy: staleness discount "
                         "exponent (1+s)^-alpha")
    ap.add_argument("--chaos", action="store_true",
                    help="engine only: inject deterministic client faults "
                         "(speed skew, dropouts, truncated local work)")
    ap.add_argument("--chaos-speed-sigma", type=float, default=1.0,
                    help="lognormal sigma of static per-client speeds")
    ap.add_argument("--chaos-jitter", type=float, default=0.1,
                    help="lognormal sigma of per-round completion jitter")
    ap.add_argument("--chaos-dropout", type=float, default=0.05,
                    help="per-round client dropout probability")
    ap.add_argument("--chaos-truncation", type=float, default=0.0,
                    help="probability a client truncates its local work")
    ap.add_argument("--halt-on-nonfinite", action="store_true",
                    help="engine only: checkpoint and stop cleanly at the "
                         "first chunk boundary after a non-finite metric")
    ap.add_argument("--uplink-codec", default="identity",
                    help="engine only: client->server delta codec "
                         "(identity | topk | topk_noef | quant | int8 | "
                         "int4 | mask | lowrank)")
    ap.add_argument("--topk-frac", type=float, default=0.05,
                    help="top-k family codecs: kept coordinate fraction "
                         "(also the adaptive ladder's capacity level)")
    ap.add_argument("--controller", default="static",
                    help="engine only: in-superstep adaptive compression "
                         "controller (static | ef_ratio | bytes_budget | "
                         "loss_trend | any registered name); non-static "
                         "defaults --uplink-codec to topk")
    ap.add_argument("--ladder", default="", metavar="V0,V1,...",
                    help="controller ladder: ascending effective levels "
                         "(topk fracs or quant bits) topping out at the "
                         "static codec parameter; empty -> default ladder")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ARCH_CONFIGS[args.arch]
    if args.scale == "tiny":
        cfg = dataclasses.replace(cfg.reduced(), vocab_size=256)
    fl = FLConfig(algorithm=args.algorithm, fusion_op=args.fusion_op,
                  local_steps=2, lr=args.lr)

    if args.engine:
        run_engine(args, cfg, dataclasses.replace(
            fl, clients_per_round=4, local_batch=args.global_batch))
        return
    shape = InputShape("custom_train", args.seq_len, args.global_batch,
                       "train")

    mesh = mesh_from_devices()
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"arch={cfg.name} ({cfg.param_count()/1e6:.1f}M params)")

    round_fn, arg_structs, in_sh, out_sh = build_train_step(
        cfg, fl, shape, mesh, dtype=jnp.float32)
    step = jax.jit(round_fn, in_shardings=in_sh, out_shardings=out_sh)

    plan = fl_plan(cfg, shape, mesh)
    bundle = make_bundle(cfg, jnp.float32)
    with jax.set_mesh(mesh):
        state = jax.jit(
            lambda k: init_global_state(bundle, fl, k),
            out_shardings=in_sh[0])(jax.random.PRNGKey(0))

        toks, src = token_stream(
            max(plan.n_clients * plan.client_batch * 4, 64), args.seq_len,
            vocab=cfg.vocab_size, n_sources=plan.n_clients)
        parts = source_partition(toks, src, plan.n_clients)
        rng = np.random.default_rng(0)
        lr_at = exp_decay_per_round(fl.lr, 0.995)

        def make_batch():
            per = []
            for c in range(plan.n_clients):
                pool = parts[c]["tokens"]
                idx = rng.choice(len(pool),
                                 (plan.local_steps, plan.client_batch))
                per.append(pool[idx])
            arr = np.stack(per)                      # [C, steps, B, S+1]
            return {"tokens": jnp.asarray(arr[..., :-1]),
                    "labels": jnp.asarray(arr[..., 1:])}

        # Pipelined round loop (repro.engine style): dispatch round r, then
        # assemble round r+1's batch on the host while the device trains,
        # and only force round r-1's metrics — the `float()` sync that used
        # to serialize host and device every round now trails by one round.
        nex = jnp.ones((plan.n_clients,), jnp.float32)
        batch = make_batch()
        pending = None
        t0 = time.perf_counter()
        for r in range(args.rounds):
            state, metrics = step(state, batch, nex, lr_at(r))
            if r + 1 < args.rounds:
                batch = make_batch()                 # overlaps device work
            if pending is not None:
                pr, pm, pt = pending
                print(f"round {pr+1:3d}  loss={float(pm['local_loss']):.4f}"
                      f"  {(time.perf_counter()-pt)*1e3:.0f} ms")
                t0 = time.perf_counter()
            pending = (r, metrics, t0)
        if pending is not None:
            pr, pm, pt = pending
            print(f"round {pr+1:3d}  loss={float(pm['local_loss']):.4f}  "
                  f"{(time.perf_counter()-pt)*1e3:.0f} ms")
    print("done")


if __name__ == "__main__":
    main()
