"""Benchmark driver: one harness per paper table/figure + kernels + roofline.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # quick mode (CI/CPU)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale rounds
    PYTHONPATH=src python -m benchmarks.run --only fig4,kernels
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (fig4_fedmmd, fig5_fedfusion, fig6_newclient,
                        fig7_compression, fig8_stragglers, kernels_bench,
                        roofline_report, table2_milestones)

SUITES = {
    "fig4": fig4_fedmmd.run,          # FedMMD vs FedAvg vs L2
    "fig5": fig5_fedfusion.run,       # FedFusion operators + Table 1
    "table2": table2_milestones.run,  # rounds-to-milestone reductions
    "fig6": fig6_newclient.run,       # new-client generalization
    "fig7": fig7_compression.run,     # wire codecs: acc vs uplink bytes
    "fig8": fig8_stragglers.run,      # straggler policies: sim-time-to-acc
    "kernels": kernels_bench.run,     # kernel microbench + overhead claim
    "roofline": roofline_report.run,  # collate dry-run artifacts
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale rounds (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(SUITES))
    args = ap.parse_args()
    enable_compile_cache()

    names = [n.strip() for n in args.only.split(",") if n.strip()] or \
        list(SUITES)
    t0 = time.time()
    for name in names:
        t = time.time()
        print(f"\n##### {name} " + "#" * 50)
        SUITES[name](quick=not args.full)
        print(f"[{name}: {time.time() - t:.1f}s]")
    print(f"\nAll benchmarks done in {time.time() - t0:.1f}s; "
          f"CSV artifacts in benchmarks/artifacts/")


if __name__ == "__main__":
    main()
