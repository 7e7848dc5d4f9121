"""Engine vs pre-PR loop: rounds/sec for the device-resident superstep.

Times the paper's per-round-accuracy workload (eval every round — Fig. 4-7
plot accuracy-per-round curves) for fedavg / fedmmd / fedfusion, each with
the identity codec and with topk+error-feedback uplink:

* baseline — ``run_federated_reference`` with ``eval_fn=_evaluate_eager``:
  the exact pre-engine loop (per-round jit dispatch, blocking ``float()``
  metrics, NumPy EF round-trip, uncompiled evaluation);
* engine — ``run_federated`` (jitted superstep chunks with eval folded
  into the scan, donated buffers, on-device EF scatter, prefetch thread,
  async metrics).

Methodology: after one warmup run (process-global op caches), each loop is
run at R1 and R2 rounds from identical fresh state; rounds/sec =
(R2 - R1) / (t2 - t1).  Both timed runs compile the same programs from
scratch (R1 and R2 are multiples of the chunk length), so compile time
cancels and the quotient is steady-state round throughput.

Quick mode deliberately uses a small, loop-overhead-bound configuration —
the paper's CNN shrunk until per-round device compute no longer masks the
loop machinery this PR replaces (per-round dispatch, blocking metrics,
NumPy EF round-trip, uncompiled eval).  Full mode times the paper-scale
CNN, where the device-compute floor (shared by both loops) bounds the
achievable ratio on CPU.

Writes ``benchmarks/artifacts/BENCH_engine.json``.  ``--check BASELINE``
compares the *speedup ratio* (engine / baseline on the same host, same
run) against a committed baseline and exits non-zero on a >20% regression
— the ratio is host-speed-independent, unlike absolute rounds/sec, so the
check is meaningful on heterogeneous CI machines.  Absolute rounds/sec are
recorded in the JSON for human eyes.

Also asserts the acceptance equivalence: the K=1 engine's final model is
bitwise-equal to the reference loop on the same seed/config.

``--mesh data=N`` times the sharded engine (client axis over a forced
N-device host mesh — the flag is translated to
``xla_force_host_platform_device_count`` BEFORE jax initializes, which is
why the env fixup below precedes every jax import) on a client-bound
config; ``--mesh-sweep data=1,2,4`` runs one point per device count (in
this process over sub-meshes of the chips on an accelerator, in one child
per point on the CPU backend) and aggregates rounds/sec scaling into the
report's ``mesh_scaling`` section.
Each multi-device point additionally records the fused-vs-unfused
collective ratio (the one-psum round vs the three-collective oracle) and
the sharded-eval eval-every-round ratio; the ``--check`` gate arms on
those once the committed baseline records them.

``--n-sweep [N1,N2,...]`` is the cohort-paged EF store's headline run:
rounds/sec at a FIXED cohort while the federation size N sweeps (default
10^3 -> 10^5, CI-sized; the store design extends to 10^6 — the per-chunk
page is K*C rows whatever N is).  The sweep runs ``ef_store="host"`` on a
:class:`repro.data.federated.TemplateClients` lazy federation (O(C) host
data too) and exits non-zero unless (a) the staged EF page bytes are
IDENTICAL at every N — the O(C·n) device-memory pin — and (b) rounds/sec
at the largest N stays >= 0.9x the smallest N.
"""
from __future__ import annotations

import os
import sys

_mesh_arg = next((a.split("=", 1)[1] if a.startswith("--mesh=")
                  else sys.argv[i + 1]
                  for i, a in enumerate(sys.argv)
                  if a == "--mesh" or a.startswith("--mesh=")), None)
if _mesh_arg is not None:   # must precede any jax import (see docstring)
    _n = int(_mesh_arg.rsplit("=", 1)[1])
    # damp intra-op threading at EVERY point (data=1 included) so the
    # curve reflects device-level sharding, not core oversubscription.
    # Best-effort: XLA CPU still runs some ops multi-threaded, so on an
    # M-core host the measurable ceiling is < M / (threads the 1-device
    # baseline already uses) — the committed baseline records cpu_count
    # and the regression gate self-disarms across host classes.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
        + (f" --xla_force_host_platform_device_count={_n}"
           if _n > 1 else ""))

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import platform      # noqa: E402
import subprocess    # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import CNN_CONFIGS
from repro.configs.base import FLConfig
from repro.data.federated import FederatedDataset
from repro.data.partition import iid_partition
from repro.fl.server import (_evaluate_eager, run_federated,
                             run_federated_reference)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import make_bundle

from benchmarks.common import ART_DIR, mnist_like, print_table

SUPERSTEP = 25  # R1/R2 are multiples, so both runs compile one chunk length
REPEATS = 3     # median-of-N rounds/sec per loop: the box's run-to-run
                # noise would otherwise dominate single measurements


def _bundle(quick: bool):
    cfg = CNN_CONFIGS["cnn_mnist"]
    if quick:
        cfg = dataclasses.replace(cfg, input_shape=(8, 8, 1),
                                  conv_channels=(2,), fc_units=(4,),
                                  dropout=0.0)
    else:
        cfg = dataclasses.replace(cfg, dropout=0.0)
    return cfg


def _data(cfg, quick: bool, seed=0):
    if quick:
        from repro.data.synth import class_images
        x, y = class_images(12, n_classes=10, shape=cfg.input_shape,
                            seed=seed, noise=0.2, template_seed=0)
        xt, yt = class_images(8, n_classes=10, shape=cfg.input_shape,
                             seed=seed + 1, noise=0.2, template_seed=0)
    else:
        x, y = mnist_like(60, seed=seed)
        xt, yt = mnist_like(10, seed=seed + 1)
    return FederatedDataset(iid_partition(x, y, 8), {"x": xt, "y": yt},
                            seed=seed)


def _configs(quick: bool):
    if quick:
        base = dict(clients_per_round=4, local_steps=1, local_batch=4,
                    lr=0.05)
    else:
        base = dict(clients_per_round=4, local_steps=4, local_batch=16,
                    lr=0.05)
    for algo, extra in (("fedavg", {}), ("fedmmd", {"mmd_lambda": 0.1}),
                        ("fedfusion", {"fusion_op": "multi"})):
        for uplink in ("identity", "topk"):
            fl = FLConfig(algorithm=algo, uplink_codec=uplink,
                          topk_frac=0.05, **extra, **base)
            yield f"{algo}x{uplink}", fl


def _timed(run, rounds):
    t0 = time.perf_counter()
    res = run(rounds)
    jax.block_until_ready(res.global_state)
    return time.perf_counter() - t0, res


def _rps(run, r1, r2, repeats=None):
    """Steady-state rounds/sec via the two-length compile-cancel trick."""
    _timed(run, r1)                      # warmup: process-global op caches
    want = repeats or REPEATS
    samples = []
    for attempt in range(3 * want):
        t1, _ = _timed(run, r1)
        t2, res = _timed(run, r2)
        # a non-positive delta means compile/scheduling jitter swallowed
        # the steady-state signal entirely — that sample carries no
        # information, so resample instead of clamping it to nonsense
        if t2 - t1 > 0:
            samples.append((r2 - r1) / (t2 - t1))
            if len(samples) >= want:
                break
    if not samples:                       # pathologically noisy host
        samples.append((r2 - r1) / max(t2 - t1, 1e-9))
    return float(np.median(samples)), res


def _mesh_config():
    """Client-bound sharding workload: ``client_sequential`` scans the
    round's clients one after another on a device, so the client axis is
    ALGORITHMICALLY serial per shard — sharding it divides the serial
    chain, which is what the sweep measures (the vmapped
    ``client_parallel`` mode already parallelizes clients inside one XLA
    program, so on CPU its scaling only reflects core oversubscription).
    No eval, identity codec: the collective under test is the FedAvg
    aggregation psum, not the wire path."""
    cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"],
                              input_shape=(24, 24, 1),
                              conv_channels=(8, 16), fc_units=(64,),
                              dropout=0.0)
    fl = FLConfig(algorithm="fedavg", clients_per_round=8, local_steps=2,
                  local_batch=8, lr=0.05)
    return cfg, fl


def _mesh_data(cfg, seed=0):
    from repro.data.synth import class_images
    x, y = class_images(24, n_classes=10, shape=cfg.input_shape, seed=seed,
                        noise=0.2, template_seed=0)
    xt, yt = class_images(8, n_classes=10, shape=cfg.input_shape,
                          seed=seed + 1, noise=0.2, template_seed=0)
    return FederatedDataset(iid_partition(x, y, 8), {"x": xt, "y": yt},
                            seed=seed)


def run_mesh_point(n_devices: int, r1: int = 10, r2: int = 40) -> dict:
    """Rounds/sec of the (sharded) engine on an ``n_devices``-wide client
    mesh — run in a process whose host was forced to that device count.

    On a real mesh (n > 1) the point also measures the two per-round
    collective knobs this engine exposes:

    * ``collective_fused_ratio`` — the fused one-psum round vs the
      three-collective oracle (``fused_collective=False``), compressed
      workload (topk uplink: the EF exchange is what gets fused away);
    * ``sharded_eval_ratio`` — eval-every-round (the paper's workload)
      with the eval batch split over the shards vs replicated eval.

    Both are bitwise/allclose-pinned equivalences (tests/test_engine.py),
    so the ratios are pure latency measurements.  On a shared-memory CPU
    host collective latency is tiny — the ratios mostly certify "no
    regression" there; the spread shows up on real interconnects.
    """
    from repro.launch.mesh import make_engine_mesh
    assert jax.device_count() >= n_devices, \
        (f"need {n_devices} devices, have {jax.device_count()} — launch "
         f"via --mesh-sweep or set xla_force_host_platform_device_count")
    cfg, fl = _mesh_config()
    bundle = make_bundle(cfg)
    mesh = make_engine_mesh(n_devices) if n_devices > 1 else None

    def run(rounds):
        return run_federated(bundle, fl, _mesh_data(cfg), rounds=rounds,
                             seed=0, eval_every=0, superstep_rounds=10,
                             mode="client_sequential", mesh=mesh)

    rps, res = _rps(run, r1, r2)
    point = {"devices": n_devices, "rps": round(rps, 2),
             "host_wait_s": res.stats["host_wait_s"],
             "clients_per_round": fl.clients_per_round,
             "mode": "client_sequential"}
    if mesh is None:
        return point

    fl_comp = dataclasses.replace(fl, uplink_codec="topk", topk_frac=0.05)

    def run_collective(rounds, fused):
        return run_federated(bundle, fl_comp, _mesh_data(cfg),
                             rounds=rounds, seed=0, eval_every=0,
                             superstep_rounds=10, mode="client_sequential",
                             mesh=mesh, fused_collective=fused)

    fused_rps, _ = _rps(lambda r: run_collective(r, True), r1, r2)
    unfused_rps, _ = _rps(lambda r: run_collective(r, False), r1, r2)
    point["rps_fused"] = round(fused_rps, 2)
    point["rps_unfused"] = round(unfused_rps, 2)
    point["collective_fused_ratio"] = round(
        fused_rps / max(unfused_rps, 1e-9), 3)

    def run_eval(rounds, sharded):
        return run_federated(bundle, fl, _mesh_data(cfg), rounds=rounds,
                             seed=0, eval_every=1, eval_examples=64,
                             superstep_rounds=10, mode="client_sequential",
                             mesh=mesh, sharded_eval=sharded)

    ev_shd, _ = _rps(lambda r: run_eval(r, True), r1, r2)
    ev_repl, _ = _rps(lambda r: run_eval(r, False), r1, r2)
    point["rps_eval_sharded"] = round(ev_shd, 2)
    point["rps_eval_replicated"] = round(ev_repl, 2)
    point["sharded_eval_ratio"] = round(ev_shd / max(ev_repl, 1e-9), 3)
    return point


def run_mesh_sweep(devices, out_dir: str) -> dict:
    """Run the mesh point per device count and aggregate the scaling curve.

    On an accelerator the points run in THIS process, each over
    ``make_engine_mesh(n)`` of the visible chips: a chip belongs to one
    process, so a child could not reach it once this one holds it.  On
    the CPU backend a point's device count is forced with an XLA flag that
    must be set before jax initializes, so each point runs in a child with
    the caller's environment."""
    points = []
    for n in devices:
        if jax.default_backend() != "cpu":
            points.append(run_mesh_point(n))
        else:
            path = os.path.join(out_dir, f"_mesh_{n}.json")
            cmd = [sys.executable, "-m", "benchmarks.bench_engine",
                   "--mesh", f"data={n}", "--out", path]
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=1800)
            if r.returncode:
                raise RuntimeError(f"mesh point {n} failed:\n{r.stdout}\n"
                                   f"{r.stderr}")
            with open(path) as f:
                points.append(json.load(f)["mesh_point"])
            os.remove(path)
        p = points[-1]
        extra = ""
        if "collective_fused_ratio" in p:
            extra = (f"  fused/unfused={p['collective_fused_ratio']}x"
                     f"  sharded-eval={p['sharded_eval_ratio']}x")
        print(f"mesh data={n}: {p['rps']:7.2f} r/s{extra}")
    one = [p for p in points if p["devices"] == 1]
    assert one, "mesh sweep needs a devices=1 point (speedup_vs_1 base)"
    base = one[0]["rps"]
    for p in points:
        p["speedup_vs_1"] = round(p["rps"] / base, 2)
    out = {"points": points,
           "max_speedup": max(p["speedup_vs_1"] for p in points)}
    fused = [p["collective_fused_ratio"] for p in points
             if "collective_fused_ratio" in p]
    if fused:
        out["collective_fused_ratio_max"] = max(fused)
    ev = [p["sharded_eval_ratio"] for p in points
          if "sharded_eval_ratio" in p]
    if ev:
        out["sharded_eval_ratio_max"] = max(ev)
    return out


def run_n_sweep(ns, r1: int = 50, r2: int = 450) -> dict:
    """Rounds/sec + EF device memory as N sweeps at a fixed cohort.

    With the dense table, every point would stage (and checkpoint-sync)
    an ``[N, n]`` device buffer — throughput and memory both scale with
    N.  With the paged store the device only ever sees the chunk's
    ``[K*C, n]`` page, so both curves must be FLAT.  ``dense_table_bytes``
    records what the dense backing would have allocated at each N (the
    page bytes / dense bytes gap is the tentpole's memory headline).
    """
    from repro.data.federated import TemplateClients
    from repro.data.synth import class_images

    # Every point compiles the SAME programs (page shapes are cohort-
    # sized, independent of N — that is the tentpole), but each engine
    # run jits fresh function objects, so every timed run would recompile
    # ~1s of XLA whose run-to-run jitter swamps the ~ms-scale steady-state
    # signal the flatness gate needs: cache even the quick compiles in the
    # persistent cache main() turned on.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    K = 10
    cfg = _bundle(True)
    bundle = make_bundle(cfg)
    fl = FLConfig(algorithm="fedavg", uplink_codec="topk", topk_frac=0.05,
                  clients_per_round=8, local_steps=1, local_batch=4, lr=0.05)
    x, y = class_images(12, n_classes=10, shape=cfg.input_shape, seed=0,
                        noise=0.2, template_seed=0)
    xt, yt = class_images(8, n_classes=10, shape=cfg.input_shape, seed=1,
                          noise=0.2, template_seed=0)
    template = {"x": x, "y": y}

    def data(n):
        return FederatedDataset(TemplateClients(template, n),
                                {"x": xt, "y": yt}, seed=0)

    points = []
    for n in ns:
        def run_point(rounds, n=n):
            return run_federated(bundle, fl, data(n), rounds=rounds, seed=0,
                                 eval_every=0, superstep_rounds=K,
                                 ef_store="host")

        rps, res = _rps(run_point, r1, r2, repeats=5)
        page = res.stats["ef_page_bytes"]
        row = page // (K * fl.clients_per_round)   # page rows = K*C
        points.append({"n_clients": int(n), "rps": round(rps, 2),
                       "ef_page_bytes": int(page),
                       "dense_table_bytes": int(n) * row,
                       "ef_store_rows": res.stats["ef_store_rows"]})
        print(f"N={n:>9,d}: {rps:7.2f} r/s  page={page / 1024:.1f} KiB  "
              f"dense table would be {n * row / (1 << 20):.1f} MiB")
    rps_lo, rps_hi = points[0]["rps"], points[-1]["rps"]
    pages = {p["ef_page_bytes"] for p in points}
    out = {"points": points, "cohort": fl.clients_per_round,
           "chunk_rounds": K, "ef_store": "host",
           "rps_flatness": round(rps_hi / max(rps_lo, 1e-9), 3),
           "flat": bool(rps_hi >= 0.9 * rps_lo),
           "page_bytes_constant": len(pages) == 1}
    print(f"n-sweep flatness: rps@maxN / rps@minN = {out['rps_flatness']} "
          f"(gate >= 0.9)   page bytes constant: "
          f"{out['page_bytes_constant']}")
    return out


def run_eval_overlap(quick: bool, cfg, bundle) -> dict:
    """Chunk-boundary stall check: eval_every=2 with the snapshot-overlap
    dispatch vs the blocking (pre-overlap) dispatch, same workload."""
    fl = FLConfig(algorithm="fedavg", clients_per_round=4,
                  local_steps=1 if quick else 4,
                  local_batch=4 if quick else 16, lr=0.05)
    ev = 32 if quick else 2048
    out = {}
    for tag, overlap in (("overlap", True), ("blocking", False)):
        rps, res = _rps(
            lambda rounds: run_federated(
                bundle, fl, _data(cfg, quick), rounds=rounds, seed=0,
                eval_every=2, eval_examples=ev, superstep_rounds=SUPERSTEP,
                overlap_eval=overlap), 24, 120 if quick else 64)
        out[f"rps_{tag}"] = round(rps, 2)
        out[f"host_wait_s_{tag}"] = res.stats["host_wait_s"]
    out["overlap_ratio"] = round(out["rps_overlap"]
                                 / max(out["rps_blocking"], 1e-9), 3)
    return out


def run_observability(quick, cfg, bundle, out_dir,
                      profile_dir=None) -> dict:
    """Instrumented engine run (repro.obs): telemetry taps + runlog span
    tracing on the topk workload, emitting the run's JSONL artifacts
    (``runlog.jsonl`` / ``comm.jsonl``) next to the report and embedding
    the round-time breakdown in it.  Also measures the telemetry on/off
    throughput ratio — informational, since the bitwise contract
    (tests/test_obs.py) already pins that "on" only adds tap arithmetic
    to the existing round program.
    """
    from repro.obs import RunLog, build_report
    fl = next(f for name, f in _configs(quick) if name == "fedavgxtopk")
    rounds = 50 if quick else 24
    os.makedirs(out_dir, exist_ok=True)
    runlog_path = os.path.join(out_dir, "runlog.jsonl")
    comm_path = os.path.join(out_dir, "comm.jsonl")

    res = run_federated(bundle, fl, _data(cfg, quick), rounds=rounds,
                        seed=0, eval_every=1,
                        eval_examples=32 if quick else 2048,
                        superstep_rounds=10, telemetry=True,
                        runlog=runlog_path, profile_dir=profile_dir)
    res.comm.save(comm_path)
    report = build_report(RunLog.load(runlog_path), res.comm.to_records())

    def run_tele(rounds, on):
        return run_federated(bundle, fl, _data(cfg, quick), rounds=rounds,
                             seed=0, eval_every=1,
                             eval_examples=32 if quick else 2048,
                             superstep_rounds=SUPERSTEP, telemetry=on)

    rps_off, _ = _rps(lambda r: run_tele(r, False), 25, 100 if quick else 50)
    rps_on, _ = _rps(lambda r: run_tele(r, True), 25, 100 if quick else 50)
    return {"round_time": report["round_time"],
            "telemetry": {"rps_off": round(rps_off, 2),
                          "rps_on": round(rps_on, 2),
                          "on_off_ratio": round(rps_on / max(rps_off, 1e-9),
                                                3)},
            "artifacts": {"runlog": runlog_path, "comm": comm_path}}


def check_bitwise(bundle, fl, cfg, quick) -> bool:
    """Acceptance: K=1 engine model bitwise-equals the reference loop."""
    ref = run_federated_reference(bundle, fl, _data(cfg, quick), rounds=6,
                                  seed=0, eval_every=1)
    eng = run_federated(bundle, fl, _data(cfg, quick), rounds=6, seed=0,
                        eval_every=1, superstep_rounds=1)
    return all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(ref.global_state), jax.tree.leaves(eng.global_state)))


def run(quick: bool = True, r1: int = None, r2: int = None,
        out_dir: str = None, profile_dir: str = None):
    cfg = _bundle(quick)
    bundle = make_bundle(cfg)
    r1 = r1 or SUPERSTEP
    r2 = r2 or (r1 + (125 if quick else 40))
    eval_examples = 32 if quick else 2048
    rows = []
    for name, fl in _configs(quick):
        base_rps, _ = _rps(
            lambda rounds: run_federated_reference(
                bundle, fl, _data(cfg, quick), rounds=rounds, seed=0,
                eval_every=1, eval_examples=eval_examples,
                eval_fn=_evaluate_eager), r1, r2)
        eng_rps, _ = _rps(
            lambda rounds: run_federated(
                bundle, fl, _data(cfg, quick), rounds=rounds, seed=0,
                eval_every=1, eval_examples=eval_examples,
                superstep_rounds=SUPERSTEP), r1, r2)
        rows.append({"config": name, "algorithm": fl.algorithm,
                     "uplink": fl.uplink_codec,
                     "baseline_rps": round(base_rps, 2),
                     "engine_rps": round(eng_rps, 2),
                     "speedup": round(eng_rps / base_rps, 2)})
        print(f"{name:22s} baseline={base_rps:7.2f} r/s  "
              f"engine={eng_rps:7.2f} r/s  speedup={eng_rps/base_rps:5.2f}x")
    speedups = [r["speedup"] for r in rows]
    geomean = float(np.exp(np.mean(np.log(speedups))))
    bitwise = check_bitwise(bundle, next(_configs(quick))[1], cfg, quick)
    # adaptive chunk sizing: what K the dispatch-overhead calibration picks
    # on this host for the quick workload (logged, not gated — it is a
    # throughput knob with results pinned chunk-size-invariant by tests)
    auto = run_federated(bundle, next(_configs(quick))[1],
                         _data(cfg, quick), rounds=8, seed=0,
                         eval_every=0, superstep_rounds="auto")
    overlap = run_eval_overlap(quick, cfg, bundle)
    obs = run_observability(quick, cfg, bundle, out_dir or ART_DIR,
                            profile_dir=profile_dir)
    report = {
        "host": {"platform": platform.platform(),
                 "device": jax.devices()[0].platform,
                 "cpu_count": os.cpu_count(),
                 "jax": jax.__version__},
        "workload": {"quick": quick, "eval_every": 1,
                     "measured_rounds": r2 - r1,
                     "superstep_rounds": SUPERSTEP},
        "results": rows,
        "geomean_speedup": round(geomean, 3),
        "k1_bitwise_equal": bool(bitwise),
        "adaptive_chunk_rounds": auto.stats["chunk_rounds"],
        "eval_overlap": overlap,
        "observability": obs,
    }
    print_table("engine vs pre-PR loop (rounds/sec)", rows)
    print(f"geomean speedup: {geomean:.2f}x   "
          f"K=1 bitwise-equal: {bitwise}")
    print(f"adaptive chunk size: {auto.stats['chunk_rounds']} rounds   "
          f"eval-overlap ratio: {overlap['overlap_ratio']}x "
          f"(host wait {overlap['host_wait_s_overlap']}s vs "
          f"{overlap['host_wait_s_blocking']}s blocking)")
    rt = obs["round_time"]
    print(f"round-time breakdown: dispatch={rt['dispatch_s']}s "
          f"metrics={rt['metrics_drain_s']}s "
          f"prefetch-stall={rt['prefetch_stall_s']}s "
          f"eval={rt['eval_s']}s of wall={rt['wall_s']}s   "
          f"telemetry on/off: {obs['telemetry']['on_off_ratio']}x")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(ART_DIR,
                                                  "BENCH_engine.json"))
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="fail if geomean speedup (or mesh scaling, when "
                         "both runs measured it) regresses >20%% vs the "
                         "committed baseline")
    ap.add_argument("--mesh", default=None, metavar="data=N",
                    help="time ONE sharded-engine point on an N-device "
                         "forced host mesh (writes {'mesh_point': ...})")
    ap.add_argument("--n-sweep", nargs="?", const="1000,10000,100000",
                    default=None, metavar="N1,N2,...",
                    help="sweep federation size at fixed cohort with the "
                         "cohort-paged EF store; exits non-zero unless "
                         "rounds/sec and EF page bytes stay flat in N")
    ap.add_argument("--mesh-sweep", default=None, metavar="data=1,2,4",
                    help="run the mesh point per device count (children "
                         "on the CPU backend) and add 'mesh_scaling' to the "
                         "report")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler trace of the instrumented "
                         "observability run into DIR")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mesh:
        n = int(args.mesh.split("=", 1)[1])
        report = {"mesh_point": run_mesh_point(n)}
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.out}")
        return

    if args.n_sweep:
        ns = [int(s) for s in args.n_sweep.split(",")]
        report = {"n_sweep": run_n_sweep(ns)}
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.out}")
        sweep = report["n_sweep"]
        if not sweep["page_bytes_constant"]:
            raise SystemExit("FAIL: EF page bytes vary with N — the paged "
                             "store is not O(C*n)")
        if not sweep["flat"]:
            raise SystemExit("FAIL: rounds/sec not flat across the N sweep "
                             f"(ratio {sweep['rps_flatness']} < 0.9)")
        return

    report = run(quick=args.quick,
                 out_dir=os.path.dirname(args.out) or ".",
                 profile_dir=args.profile)
    if args.mesh_sweep:
        devices = [int(d) for d in
                   args.mesh_sweep.split("=", 1)[1].split(",")]
        report["mesh_scaling"] = run_mesh_sweep(devices,
                                                os.path.dirname(args.out)
                                                or ".")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if not report["k1_bitwise_equal"]:
        raise SystemExit("FAIL: K=1 engine is not bitwise-equal to the "
                         "reference loop")
    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
        same_host_class = (baseline.get("host", {}).get("cpu_count")
                           == os.cpu_count())

        def gate(name, got, floor):
            if got >= floor:
                print(f"regression check OK: {name} {got:.2f} >= "
                      f"{floor:.2f}")
                return
            msg = f"{name} {got:.2f} < floor {floor:.2f}"
            if same_host_class:
                raise SystemExit("FAIL: " + msg)
            # ratios still shift with the host's compute floor; a baseline
            # recorded on a different machine class cannot gate reliably —
            # warn, and refresh the baseline from this host class.
            print(f"WARN (not gating): {msg}; baseline host has cpu_count="
                  f"{baseline.get('host', {}).get('cpu_count')}, this host "
                  f"{os.cpu_count()} — refresh "
                  f"benchmarks/baselines/BENCH_engine.json on this host "
                  f"class to arm the gate")

        gate("geomean speedup", report["geomean_speedup"],
             0.8 * baseline["geomean_speedup"])
        if "mesh_scaling" in report and "mesh_scaling" in baseline:
            gate("mesh max speedup", report["mesh_scaling"]["max_speedup"],
                 0.8 * baseline["mesh_scaling"]["max_speedup"])
            # collective-layout gates: self-arm once the committed
            # baseline records the ratios (same-host-class rule applies)
            for key in ("collective_fused_ratio_max",
                        "sharded_eval_ratio_max"):
                if key in report["mesh_scaling"] \
                        and key in baseline["mesh_scaling"]:
                    gate(key, report["mesh_scaling"][key],
                         0.8 * baseline["mesh_scaling"][key])


if __name__ == "__main__":
    main()
