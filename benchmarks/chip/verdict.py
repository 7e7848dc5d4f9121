"""The numbers that decide ``correct``: the timed path against the reference.

The program's first compiled step (one superstep of ``superstep_rounds``
rounds from the seed, run in set-up through the very entry and program
the window then drives) is compared with the reference following the
same rounds (``reference.py``, at the precision the configuration
states).  Compared, each against its limit in ``limits/<cell>.json``:

* ``loss1_gap`` -- the first round's mean local training loss, the
  relative gap;
* ``eval1_gap`` -- the eval loss over the test set after the first
  round, the relative gap;
* ``change_gap`` -- the norm of each leaf's change over the step, the
  worst gap between the program's norm and the reference's, measured
  against the larger of that leaf's reference norm and the median leaf's.
  Leaves whose reference change is under a thousandth of the median
  leaf's are left out (their change is round-off).

Later rounds are not compared: local SGD at the TPU's default precision
turns the last-bit differences of two programs into percent-sized ones
within two or three rounds (PERF.md section 6); their gaps are printed
(``loss_gap`` and ``eval_loss_gap`` over the first ``LOSS_ROUNDS``, the
median leaf's ``change_median_gap``).
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
LOSS_ROUNDS = 3
STILL_LEAF = 1e-3


def load_limits(cell):
    """The cell's limits; a cell without a limits file has none, and no
    run of it is correct."""
    path = os.path.join(HERE, "limits", f"{cell}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)["limits"]


def _rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def numbers(prog_hist, prog_final, ref0, ref_hist, ref_final):
    """Every comparison number, and the per-leaf change gaps behind
    ``change_gap``.  ``prog_final``/``ref0``/``ref_final``: host leaves by
    path (see ``reference.leaf_dict``); the histories: per-round dicts
    with ``local_loss`` and ``loss``."""
    if set(prog_final) != set(ref0):
        raise KeyError(f"the program's leaves {sorted(prog_final)} are not "
                       f"the reference's {sorted(ref0)}")
    n = min(LOSS_ROUNDS, len(ref_hist))
    per_round = {key: [_rel(p[key], r[key]) for p, r in zip(prog_hist,
                                                             ref_hist)]
                 for key in ("local_loss", "loss")}
    out = {
        "loss_gap": max(per_round["local_loss"][:n]),
        "loss1_gap": per_round["local_loss"][0],
        "eval_loss_gap": max(per_round["loss"][:n]),
        "eval1_gap": per_round["loss"][0],
    }
    ref_norm = {k: float(np.linalg.norm(ref_final[k] - ref0[k]))
                for k in ref0}
    prog_norm = {k: float(np.linalg.norm(prog_final[k] - ref0[k]))
                 for k in ref0}
    median = float(np.median(list(ref_norm.values())))
    counted = [k for k in ref0 if ref_norm[k] >= STILL_LEAF * median]
    leaf_gaps = {k: abs(prog_norm[k] - ref_norm[k])
                 / max(ref_norm[k], median) for k in counted}
    out["change_gap"] = max(leaf_gaps.values())
    out["change_median_gap"] = float(np.median(list(leaf_gaps.values())))
    return out, {"leaf_gaps": leaf_gaps, "per_round": per_round,
                 "left_out": sorted(set(ref0) - set(counted)),
                 "ref_change_norms": ref_norm,
                 "prog_change_norms": prog_norm}


def judge(values, limits):
    """(correct, checks): each limited number beside its limit.  A number
    that is not finite fails."""
    checks = {}
    ok = bool(limits)
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and bool(good)
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
