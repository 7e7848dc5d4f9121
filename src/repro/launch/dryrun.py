import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) pair on the
production meshes and record memory/cost/roofline terms.

This file — and ONLY this file — forces 512 placeholder host devices, which
is why the env var is set before any other import.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]

Results: one JSON per (arch, shape, mesh) under benchmarks/artifacts/dryrun/.
"""
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCH_CONFIGS, INPUT_SHAPES  # noqa: E402
from repro.configs.base import ALGORITHM_NAMES, FLConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh    # noqa: E402
from repro.launch.specs import skip_reason            # noqa: E402
from repro.launch.steps import build_step             # noqa: E402
from repro.roofline import analyze                    # noqa: E402

# the chip the production meshes are made of: roofline terms divide by its
# published peaks (repro.roofline.PEAKS), whatever device compiles here
TARGET_DEVICE_KIND = "TPU v5 lite"

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")


def default_fl() -> FLConfig:
    # the paper's main technique, conv operator (most representative)
    return FLConfig(algorithm="fedfusion", fusion_op="conv", local_steps=2)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            fl: FLConfig | None = None, save: bool = True,
            save_hlo: bool = False, remat: str = "none",
            serve_ep: bool = False, shard_capacity: bool = False,
            moe_dispatch: str = "gather", tag: str = "") -> dict:
    cfg = dataclasses.replace(ARCH_CONFIGS[arch], remat=remat,
                              serve_expert_parallel=serve_ep,
                              moe_shard_capacity=shard_capacity,
                              moe_dispatch=moe_dispatch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    reason = skip_reason(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if tag:
        rec["tag"] = tag
    if reason:
        rec.update(status="skip", reason=reason)
        return _save(rec) if save else rec

    fl = fl or default_fl()
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, in_sh, out_sh = build_step(cfg, fl, shape, mesh)
        with jax.set_mesh(mesh):   # sharding-constraint P specs resolve here
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        from repro.fl.api import make_algorithm  # noqa: E402 (env-var file)
        chips = mesh.size
        roof = analyze(compiled, cfg, shape, mesh_name, chips, mesh,
                       two_stream=make_algorithm(fl.algorithm).two_stream,
                       device_kind=TARGET_DEVICE_KIND)
        mem = compiled.memory_analysis()
        rec.update(
            status="ok",
            t_lower_s=round(t_lower, 1),
            t_compile_s=round(t_compile, 1),
            memory={k: int(getattr(mem, k, 0)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")},
            roofline=roof.to_dict(),
        )
        per_chip = (rec["memory"]["argument_size_in_bytes"]
                    + rec["memory"]["temp_size_in_bytes"]) / chips
        rec["bytes_per_chip"] = int(per_chip)
        rec["fits_16gb_hbm"] = bool(per_chip < 16e9)
        if save_hlo:
            os.makedirs(ART_DIR, exist_ok=True)
            hsuffix = f"__{tag}" if tag else ""
            hpath = os.path.join(
                ART_DIR,
                f"{arch}__{shape_name}__{mesh_name}{hsuffix}.hlo.txt")
            with open(hpath, "w") as f:
                f.write(compiled.as_text())
            rec["hlo_path"] = hpath
    except Exception as e:  # noqa: BLE001 — a dry-run failure is a finding
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return _save(rec) if save else rec


def run_superstep(multi_pod: bool, compressed: bool = True,
                  save: bool = True, n_rounds: int = 8,
                  fused: bool = True, sharded_eval: bool = True) -> dict:
    """Dry-run the SHARDED federated superstep on a production mesh.

    Lowers (never compiles — no real devices needed beyond the forced
    host placeholders) the ``shard_map``-wrapped K-round superstep with
    abstract chunk arguments: the client axis over ``data``/``pod``, the
    full-federation EF table row-sharded by client id in the resident
    scratch-row layout, shard-split evaluation folded into the scan, and
    the fused one-psum-per-round collective on by default (``fused=False``
    lowers the three-collective oracle).  Catches sharding-spec and shape
    regressions of ``repro.engine.sharded`` against the 16x16 / 2x16x16
    meshes on a CPU box.
    """
    import jax.numpy as jnp
    from repro.compress import make_codec
    from repro.configs import CNN_CONFIGS
    from repro.core.rounds import init_global_state
    from repro.engine.evaljit import make_eval_fn
    from repro.engine.sharded import client_sharding, make_sharded_superstep
    from repro.launch.sharding import (chunk_shardings, ef_table_sharding,
                                       eval_batch_sharding)
    from repro.models.registry import make_bundle

    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": "cnn_mnist", "shape": "superstep", "mesh": mesh_name,
           "tag": ("topk" if compressed else "plain")
                  + ("" if fused else "-unfused")}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        shard = client_sharding(mesh)
        n_clients_round = 32          # divides 16 (data) and 32 (pod*data)
        n_federation = 64
        cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"], dropout=0.0)
        fl = FLConfig(algorithm="fedavg", clients_per_round=n_clients_round,
                      local_steps=2, local_batch=8,
                      uplink_codec="topk" if compressed else "identity",
                      topk_frac=0.05)
        bundle = make_bundle(cfg)
        state = jax.eval_shape(lambda k: init_global_state(bundle, fl, k),
                               jax.random.PRNGKey(0))
        K, C, S, B = n_rounds, n_clients_round, fl.local_steps, fl.local_batch
        H, W, Ch = cfg.input_shape
        batches = {
            "x": jax.ShapeDtypeStruct((K, C, S, B, H, W, Ch), jnp.float32),
            "y": jax.ShapeDtypeStruct((K, C, S, B), jnp.int32),
        }
        sizes = jax.ShapeDtypeStruct((K, C), jnp.float32)
        lrs = jax.ShapeDtypeStruct((K,), jnp.float32)
        sh_batch, sh_repl = chunk_shardings(mesh)
        # eval folded into the scan, batch split over the client shards
        eval_fn = (make_eval_fn(bundle, fl, shard=shard)
                   if sharded_eval else None)
        bucket = 512                  # divides 16 and 32 client shards
        test_args = ()
        test_sh = ()
        if sharded_eval:
            test_args = (
                {"x": jax.ShapeDtypeStruct((bucket, H, W, Ch), jnp.float32),
                 "y": jax.ShapeDtypeStruct((bucket,), jnp.int32)},
                jax.ShapeDtypeStruct((bucket,), jnp.bool_))
            ev_sh = eval_batch_sharding(mesh)
            test_sh = (ev_sh, ev_sh)

        if compressed:
            uplink = make_codec(fl.uplink_codec, topk_frac=fl.topk_frac)
            downlink = make_codec(fl.downlink_codec)
            uplink.bind(state["model"])
            downlink.bind(state["model"])
            # resident scratch-row layout: one extra row per shard block
            n_loc = n_federation // shard.n_shards
            ef = [jax.ShapeDtypeStruct(
                      ((n_loc + 1) * shard.n_shards,) + z.shape, z.dtype)
                  for z in jax.eval_shape(uplink.init_state)]
            fn = make_sharded_superstep(bundle, fl, "client_parallel", K,
                                        mesh, uplink=uplink,
                                        downlink=downlink, eval_fn=eval_fn,
                                        fused_collective=fused)
            args = (state, ef, state["model"], batches, sizes, lrs,
                    jax.ShapeDtypeStruct((K, C), jnp.int32),
                    jax.ShapeDtypeStruct((K,), jnp.int32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32)) + test_args
            ef_sh = ef_table_sharding(mesh)
            in_sh = (sh_repl, ef_sh, sh_repl, sh_batch, sh_batch, sh_repl,
                     sh_repl, sh_repl, sh_repl) + test_sh
        else:
            fn = make_sharded_superstep(bundle, fl, "client_parallel", K,
                                        mesh, eval_fn=eval_fn,
                                        fused_collective=fused)
            args = (state, batches, sizes, lrs) + test_args
            in_sh = (sh_repl, sh_batch, sh_batch, sh_repl) + test_sh

        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        out = jax.eval_shape(fn, *args)
        rec.update(
            status="ok",
            t_lower_s=round(time.time() - t0, 1),
            fused_collective=fused,
            sharded_eval=sharded_eval,
            client_shards=shard.n_shards,
            clients_per_shard=n_clients_round // shard.n_shards,
            ef_rows_per_shard=(n_federation // shard.n_shards + 1
                               if compressed else 0),
            out_avals=[str(x.shape) for x in jax.tree_util.tree_leaves(out)
                       ][:4],
            hlo_ops=len(lowered.as_text()) > 0,
        )
    except Exception as e:  # noqa: BLE001 — a dry-run failure is a finding
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return _save(rec) if save else rec


def _save(rec: dict) -> dict:
    os.makedirs(ART_DIR, exist_ok=True)
    suffix = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        ART_DIR, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCH_CONFIGS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--algorithm", default="fedfusion",
                    choices=sorted(ALGORITHM_NAMES))
    ap.add_argument("--fusion-op", default="conv",
                    choices=("conv", "multi", "single"))
    ap.add_argument("--save-hlo", action="store_true",
                    help="dump compiled HLO text next to the JSON record")
    ap.add_argument("--remat", default="none",
                    choices=("none", "attn", "layer"),
                    help="activation-checkpoint policy (perf knob)")
    ap.add_argument("--serve-ep", action="store_true",
                    help="expert-parallel sharding for prefill/decode")
    ap.add_argument("--moe-shard-capacity", action="store_true",
                    help="shard MoE capacity dim over 'model' (perf knob)")
    ap.add_argument("--moe-a2a", action="store_true",
                    help="shard_map all-to-all expert dispatch (perf knob)")
    ap.add_argument("--tag", default="",
                    help="suffix for the artifact filename (perf variants)")
    ap.add_argument("--superstep", action="store_true",
                    help="dry-run the sharded federated superstep "
                         "(repro.engine.sharded) on the production meshes "
                         "instead of a model step")
    args = ap.parse_args()
    fl = FLConfig(algorithm=args.algorithm, fusion_op=args.fusion_op,
                  local_steps=2)

    if args.superstep:
        pods = [True] if args.multi_pod else [False, True]
        failed = False
        for mp in pods:
            # fused one-psum path (the engine default) for plain + topk,
            # plus the three-collective oracle layout on the compressed
            # round (the fused path's equivalence baseline)
            points = [(False, True), (True, True), (True, False)]
            for compressed, fused in points:
                rec = run_superstep(mp, compressed=compressed, fused=fused)
                tag = f"{rec['mesh']:8s} {rec['tag']:13s}"
                if rec["status"] == "ok":
                    print(f"superstep {tag} ok  lower={rec['t_lower_s']}s "
                          f"shards={rec['client_shards']} "
                          f"C/shard={rec['clients_per_shard']} "
                          f"ef-rows/shard={rec['ef_rows_per_shard']}")
                else:
                    failed = True
                    print(f"superstep {tag} ERROR {rec['error']}")
                    print(rec.get("traceback", ""))
        if failed:
            raise SystemExit(1)
        return

    if args.all:
        pods = [False, True]
        if args.single_pod_only:
            pods = [False]
        if args.multi_pod_only:
            pods = [True]
        for arch in ARCH_CONFIGS:
            for shape in INPUT_SHAPES:
                for mp in pods:
                    rec = run_one(arch, shape, mp, fl,
                                  save_hlo=args.save_hlo, remat=args.remat,
                                  serve_ep=args.serve_ep,
                                  shard_capacity=args.moe_shard_capacity,
                                  tag=args.tag)
                    _report(rec)
        return
    rec = run_one(args.arch, args.shape, args.multi_pod, fl,
                  save_hlo=args.save_hlo, remat=args.remat,
                  serve_ep=args.serve_ep,
                  shard_capacity=args.moe_shard_capacity,
                  moe_dispatch="a2a" if args.moe_a2a else "gather",
                  tag=args.tag)
    _report(rec, verbose=True)


def _report(rec: dict, verbose: bool = False) -> None:
    tag = f"{rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:8s}"
    if rec["status"] == "skip":
        print(f"{tag} SKIP ({rec['reason']})")
    elif rec["status"] == "error":
        print(f"{tag} ERROR {rec['error']}")
        if verbose:
            print(rec.get("traceback", ""))
    else:
        r = rec["roofline"]
        print(f"{tag} ok  compile={rec['t_compile_s']}s "
              f"bytes/chip={rec['bytes_per_chip']/1e9:.2f}GB "
              f"t_comp={r['t_compute']*1e3:.2f}ms t_mem={r['t_memory']*1e3:.2f}ms "
              f"t_coll={r['t_collective']*1e3:.2f}ms -> {r['bottleneck']}"
              f" useful={r['useful_ratio']:.2f}")
        if verbose:
            print(json.dumps(rec["memory"], indent=1))
            print(json.dumps(r["coll_breakdown"], indent=1))


if __name__ == "__main__":
    main()
