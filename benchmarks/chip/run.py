#!/usr/bin/env python3
"""On-chip benchmark of ``FederatedTrainer.fit``: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  A cell (``BENCHMARK.json``) names a
configuration, a traffic mix and a chip count.  The run

1. makes the federation's arrays from ``--seed`` (``gen.py``) and hands
   them to the program as its own ``FederatedDataset``;
2. builds one ``FederatedTrainer`` and runs one warm-up ``fit`` of one
   superstep from the seed: it compiles (or loads from the compile cache
   kept in the checkout) every program the window runs, and its result is
   what ``correct`` is judged on;
3. sizes the timed fit from the warm-up's rate, a whole number of
   supersteps that fills ``--seconds``, and measures it from the end of
   its first chunk's dispatch (the program's ``chunk.dispatch`` span:
   tracing, lowering and cache loading lie before) to the final state
   being ready;
4. reads the chips' peak memory, frees the program's state and runs the
   plain reference over the warm-up's rounds (``reference.py``).

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the profiler traces the timed window and the
metrics are the cell's per-layer metrics, each read by
``metrics/<name>.py``.  The last line of standard output is one JSON
object; the numbers ``correct`` compared, each beside its limit, are the
last lines of standard error and the result's last key.  With no TPU, or
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

ROOT = spec.ROOT


class NoChip(SystemExit):
    pass


def require_chips(n):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform} devices only")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, JAX sees {len(devs)}")
    return devs[:n]


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed directory of the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def window_log_class():
    """A ``RunLog`` sink that calls back when a fit's first
    ``chunk.dispatch`` span ends (the window opens there) and, while the
    profiler traces, mirrors every span into the trace."""
    import jax

    from repro.obs.runlog import RunLog

    class WindowLog(RunLog):
        def __init__(self):
            super().__init__()
            self.on_open = None
            self.annotate = False

        def arm(self, on_open, annotate=False):
            self.on_open = on_open
            self.annotate = annotate

        def span(self, name, **attrs):
            inner = super().span(name, **attrs)

            @contextlib.contextmanager
            def spanned():
                ann = (jax.profiler.TraceAnnotation(name) if self.annotate
                       else contextlib.nullcontext())
                with ann, inner:
                    yield
                if name == "chunk.dispatch" and self.on_open is not None:
                    hook, self.on_open = self.on_open, None
                    hook()
            return spanned()

    return WindowLog


def program_objects(cell, seed, log):
    """The program under test, built from the cell: bundle, FL config,
    data set and run options."""
    from repro.configs.base import CNNConfig, FLConfig
    from repro.data.federated import FederatedDataset
    from repro.fl.api import (EngineOptions, EvalOptions, FederatedTrainer,
                              RunOptions)
    from repro.models.registry import make_bundle

    import gen
    m, t = cell.model, cell.traffic
    cfg = CNNConfig(name=cell.config["name"],
                    input_shape=tuple(m["input_shape"]),
                    conv_channels=tuple(m["conv_channels"]),
                    pool_size=m["pool_size"], pool_stride=m["pool_stride"],
                    fc_units=tuple(m["fc_units"]), n_classes=m["n_classes"],
                    dropout=m["dropout"])
    fl = FLConfig(**{k: tuple(v) if isinstance(v, list) else v
                     for k, v in t["fl"].items()})
    parts, test = gen.federation(m, t["federation"], seed)
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_engine_mesh
        mesh = make_engine_mesh(cell.chips)
    opts = RunOptions(
        seed=seed,
        eval=EvalOptions(every=t["eval_every"], examples=t["eval_examples"]),
        engine=EngineOptions(superstep_rounds=t["superstep_rounds"],
                             ef_store=t["ef_store"], mesh=mesh, runlog=log))
    trainer = FederatedTrainer(make_bundle(cfg), fl,
                               FederatedDataset(parts, test, seed=seed), opts)
    return trainer, parts, test


def first_step(cell, seed, log):
    """Set-up: the program built from the cell and driven from the seed
    through its first compiled step (one superstep), by the entry the
    window then drives.  That compiles every program of the window (or
    loads it from the compile cache), and its outcome is what ``correct``
    judges.

    ``chunk_s``: what one chunk took, the larger of its device time (from
    the end of its dispatch to its state being ready) and the wait for
    its staging, since the host stages later chunks while the device
    trains."""
    import jax

    import reference
    trainer, parts, test = program_objects(cell, seed, log)
    opened = {}
    log.arm(lambda: opened.__setitem__("t", time.perf_counter()))
    res = trainer.fit(cell.traffic["superstep_rounds"])
    jax.block_until_ready(res.global_state)
    chunk_s = max(time.perf_counter() - opened["t"],
                  res.stats["host_wait_s"])
    out = {"trainer": trainer, "parts": parts, "test": test,
           "history": [dict(h) for h in res.comm.history],
           "final": reference.leaf_dict(res.global_state),
           "chunk_s": chunk_s}
    # the fit's device buffers (its EF table among them) sit in reference
    # cycles of the engine's closures: free them before the next fit
    del res
    gc.collect()
    return out


def host_timeline(records, use0, use1):
    """What the host did in the timed fit, for finding why a run is slow:
    the spread of the intervals between chunk dispatches and of the
    prefetch thread's staging times (the program's spans), and the
    process's CPU seconds over the window."""
    def spread(xs):
        xs = sorted(xs)
        return [xs[0], xs[len(xs) // 2], xs[-1]] if xs else []

    starts = [r["t0"] for r in records
              if r.get("kind") == "span" and r["name"] == "chunk.dispatch"]
    stage = [r["dur"] for r in records
             if r.get("kind") == "span" and r["name"] == "prefetch.stage"]
    return {
        "dispatch_interval_s_min_median_max": spread(
            [b - a for a, b in zip(starts, starts[1:])]),
        "stage_s_min_median_max": spread(stage),
        "cpu_s": (use1.ru_utime - use0.ru_utime)
        + (use1.ru_stime - use0.ru_stime)}


def peak_bytes(devices):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_cell(cell, seed, seconds, trace, *, devices=None, limits=None,
             out=None, err=None, trace_dir=None):
    """One run; returns the result object (also printed last on ``out``).

    ``devices``: the chips to use; None looks for them and exits if they
    are not there.  ``limits``: the correctness limits (default: the
    cell's file)."""
    out = out or sys.stdout
    err = err or sys.stderr
    if devices is None:
        devices = require_chips(cell.chips)
    import jax
    import numpy as np

    import reference
    import verdict
    import xtrace
    if devices[0].platform == "tpu":
        use_compile_cache()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    t = cell.traffic
    k = t["superstep_rounds"]

    log = window_log_class()()
    first = first_step(cell, seed, log)
    trainer, parts, test = first.pop("trainer"), first["parts"], first["test"]
    setup_peak = peak_bytes(devices)
    n_chunks = max(2, math.ceil(seconds / first["chunk_s"]))
    rounds = n_chunks * k
    opened = {}

    # -- the window ------------------------------------------------------
    trace_dir = trace_dir or os.path.join(ROOT, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2

    def open_window():
        opened["timed"] = time.perf_counter()
        opened["usage"] = resource.getrusage(resource.RUSAGE_SELF)
        if trace:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    log.arm(open_window, annotate=bool(trace))
    compiles = xtrace.CompileCounter()
    n_records = len(log.records())
    with compiles.counting(lambda: "timed" in opened):
        res = trainer.fit(rounds)
        jax.block_until_ready(res.global_state)
        t_end = time.perf_counter()
        use_end = resource.getrusage(resource.RUSAGE_SELF)
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - opened["timed"]
    setup_s = opened["timed"] - T_START
    peak = peak_bytes(devices)
    hist = res.comm.history
    failed = sum(1 for h in hist if not all(
        np.isfinite(h[key]) for key in ("local_loss", "loss")))
    stats = dict(res.stats)
    del res, trainer
    gc.collect()
    print(f"window: {rounds} rounds in {n_chunks} chunks of {k}, "
          f"{window_s!r} s; a chunk in set-up took {first['chunk_s']!r} s; "
          f"compiles in the window {compiles.n} {compiles.names}; set-up "
          f"{setup_s!r} s; peak bytes after the first step {setup_peak}",
          file=out, flush=True)
    print("host in the window: " + json.dumps(host_timeline(
        log.records()[n_records:], opened["usage"], use_end)),
        file=out, flush=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": rounds, "failed": failed}
    if trace:
        reduced = xtrace.reduce_dir(trace_dir, [d.id for d in devices])
        # what the run saw, for the per-layer readers
        obs = types.SimpleNamespace(
            cell=cell, model=cell.model, traffic=t, rounds=rounds,
            window_s=window_s, rounds_per_s=rounds / window_s,
            # the rounds the trace saw: it starts once the window is open
            traced_rounds=rounds * reduced.span_s() / window_s,
            chips=len(devices), device_kind=devices[0].device_kind,
            stats=stats, trace=reduced)
        metrics = {}
        for entry in cell.per_layer:
            value = spec.reader(entry["name"]).read(obs)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.span_s()
        print(f"trace: device ops from first to last {reduced.span_s()!r} s "
              f"of the {window_s!r}-s window, busy {reduced.busy_s()!r} s",
              file=out, flush=True)
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"rounds_per_s": rounds / window_s,
                  "peak_hbm_mb": peak / 1e6, "setup_s": setup_s}
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device

    # -- correct: the warm-up's superstep against the reference ----------
    t_ref = time.perf_counter()
    ref0, ref_final, ref_hist = reference.run_reference(
        cell.model, t, parts, test, seed, k,
        reference.for_config(cell.config))
    values, detail = verdict.numbers(first["history"], first["final"], ref0,
                                     ref_hist, ref_final)
    ok, checks = verdict.judge(values, limits if limits is not None
                               else verdict.load_limits(cell.name))
    print(f"reference: {k} rounds in {time.perf_counter() - t_ref!r} s; "
          f"readings {json.dumps(values)}; leaf gaps "
          f"{json.dumps(detail['leaf_gaps'])}; left out "
          f"{detail['left_out']}", file=out, flush=True)
    print("program history "
          + json.dumps(first["history"][:verdict.LOSS_ROUNDS]),
          file=out)
    print("reference history " + json.dumps(ref_hist[:verdict.LOSS_ROUNDS]),
          file=out)
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    cell = spec.Cell.load(args.workload)
    try:
        run_cell(cell, args.seed, args.seconds, args.trace)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
