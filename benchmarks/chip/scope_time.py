"""Device time under one of the program's round scopes.

The program names the four parts of a federated round with fixed
``jax.named_scope`` names (``local_train``, ``codec``, ``aggregate``,
``eval``); a device op lies under a part when the part's name is one
segment of its ``tf_op`` name stack, whatever transform wraps it
(``vmap(codec)``, ``transpose(jvp(local_train))``).  A program without
the scopes has no such op, and its readers return None.
"""
from __future__ import annotations

import re


def under(name):
    """``op -> bool``: the op's name stack holds the scope ``name``."""
    segment = re.compile(r"(?:^|[/(])" + re.escape(name) + r"(?=$|[/):])")
    return lambda op: segment.search(op.tf_op) is not None


def ms_per_round(obs, name):
    """Device milliseconds per traced round of the ops under ``name``
    (each with what nests in it, counted once; the busiest chip), or None
    where the trace holds none."""
    match = under(name)
    if obs.trace is None or obs.trace.count(match) == 0:
        return None
    return 1000.0 * obs.trace.op_seconds(match) / obs.traced_rounds
