#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--variants control,...]

For each of ``--seeds``: the program's first step (the set-up of a run,
``run.first_step``) against the reference, the lower readings.  For each
of ``--control-seeds`` also, in the program's place: the control (the
reference in bfloat16) and the faults a training cell can have (half of
each batch left out of the loss; under top-k, the error feedback's
residual dropped or stored in another client's row), the upper
readings.  One JSON line per reading, each judged by the cell's limits
(``verdict.judge``), then the largest lower and the smallest upper
reading of each number and, for each kind, the seeds its readings were
judged correct on.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import spec  # noqa: E402

NUMBERS = ("loss_gap", "loss1_gap", "eval_loss_gap", "eval1_gap",
           "change_gap", "change_median_gap")


def readings(cell, seeds, control_seeds, devices=None, out=sys.stdout,
             only=None):
    import reference
    import verdict
    if devices is None:
        devices = run.require_chips(cell.chips)
    run.use_compile_cache()
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    k = cell.traffic["superstep_rounds"]
    ref = reference.for_config(cell.config)
    variants = {"control": reference.CONTROL,
                "half_batch": dataclasses.replace(ref, half_batch=True),
                "ref_highest": dataclasses.replace(ref, matmul="highest")}
    if cell.traffic["fl"]["uplink_codec"] == "topk":
        variants["ef_dropped"] = dataclasses.replace(ref, ef_fault="dropped")
        variants["ef_wrong_rows"] = dataclasses.replace(
            ref, ef_fault="wrong_rows")
    if only is not None:
        variants = {name: recipe for name, recipe in variants.items()
                    if name in only}
    limits = verdict.load_limits(cell.name)
    runs = {name: reference.Run(cell.model, cell.traffic, recipe)
            for name, recipe in [("reference", ref)]
            + list(variants.items())}
    rows = []
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        log = run.window_log_class()()
        first = run.first_step(cell, seed, log)
        del first["trainer"]
        t_prog = time.perf_counter() - t0
        data = (first["parts"], first["test"], seed, k)
        t0 = time.perf_counter()
        ref0, ref_final, ref_hist = runs["reference"].run(*data)
        t_ref = time.perf_counter() - t0
        kinds = [("program", first["history"], first["final"])]
        refs = {"reference": (ref_hist, ref_final)}
        if seed in control_seeds:
            for name in variants:
                _, fin, hist = runs[name].run(*data)
                kinds.append((name, hist, fin))
                if name == "ref_highest":
                    refs["highest"] = (hist, fin)
        for kind, hist, fin in kinds:
            if kind == "program" and seed not in seeds:
                continue
            for against, (r_hist, r_final) in refs.items():
                values, detail = verdict.numbers(hist, fin, ref0, r_hist,
                                                 r_final)
                ok, checks = verdict.judge(values, limits)
                row = {"seed": seed, "kind": kind, "against": against,
                       **values, "correct": ok,
                       "over_limit": sorted(n for n, c in checks.items()
                                            if not c["value"] <= c["limit"]),
                       "t_program_s": t_prog,
                       "t_reference_s": t_ref, **detail}
                rows.append(row)
                print(json.dumps(row), file=out, flush=True)
    summary = {}
    for kind, against in {(r["kind"], r["against"]) for r in rows}:
        sel = [r for r in rows
               if r["kind"] == kind and r["against"] == against]
        pick = max if kind == "program" else min
        key = f"{kind} against {against}"
        summary[key] = {n: pick(r[n] for r in sel) for n in NUMBERS}
        summary[key]["seeds"] = len(sel)
        summary[key]["correct_on"] = sorted(r["seed"] for r in sel
                                            if r["correct"])
    print("summary " + json.dumps(summary), file=out, flush=True)
    return rows, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--variants", default="",
                    help="the controls and faults to run (default: all)")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    readings(spec.Cell.load(args.workload), ints(args.seeds),
             ints(args.control_seeds),
             only=set(args.variants.split(",")) if args.variants else None)


if __name__ == "__main__":
    main()
