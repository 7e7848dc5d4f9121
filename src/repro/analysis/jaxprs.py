"""Jaxpr-walking substrate for the static invariant passes.

Every check in ``repro.analysis`` that operates before XLA — collective
counts, host-callback detection, dtype drift — is a walk over the traced
jaxpr of a superstep.  This module is the one place that walk lives:
``iter_eqns`` descends into every sub-jaxpr an equation carries (scan and
while bodies, cond branches, pjit/closed-call bodies, custom-vjp
closures), so a psum hidden three levels deep in a scanned round fn
counts exactly like a top-level one.

The public :func:`count_collectives` is the exported replacement for the
five copy-pasted ``count_psums`` helpers the subprocess invariant tests
grew between PR 5 and PR 9 — they now all import it from here.
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax

# Cross-device collective primitives as they appear in jaxprs.  ``psum``
# is the only one the engine is ever allowed to emit; the rest are listed
# so a sneaky all_gather trips the same counters.
COLLECTIVE_PRIMITIVES: Tuple[str, ...] = (
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "reduce_scatter", "ppermute", "pgather", "psum_scatter",
)

# Host-synchronizing primitives: anything that round-trips to Python or
# the host runtime from inside a traced computation.
HOST_SYNC_PRIMITIVES: Tuple[str, ...] = (
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
    "debug_print",
)


def _as_jaxpr(jaxpr):
    """Accept a Jaxpr or a ClosedJaxpr (``jax.make_jaxpr`` output)."""
    return getattr(jaxpr, "jaxpr", jaxpr)


def eqn_subjaxprs(eqn) -> Iterator:
    """The sub-jaxprs one equation carries (scan / while bodies, cond
    branches, pjit and custom-derivative closures)."""
    is_sub = lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")
    for v in eqn.params.values():
        for j in jax.tree_util.tree_leaves(v, is_leaf=is_sub):
            inner = (j.jaxpr if hasattr(j, "jaxpr")
                     else (j if hasattr(j, "eqns") else None))
            if inner is not None:
                yield inner


def subjaxprs(jaxpr) -> Iterator:
    """Immediate sub-jaxprs referenced by ``jaxpr``'s equations."""
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield from eqn_subjaxprs(eqn)


def leaf_eqns_with_name_stacks(jaxpr, prefix: str = "") -> Iterator:
    """``(name_stack, eqn)`` for every equation that carries no
    sub-jaxpr, recursively, with its full name stack: the enclosing
    equations' stacks joined to its own, as lowering composes them into
    a device op's ``tf_op`` (``vmap(local_train)/transpose(jvp())``)."""
    for eqn in _as_jaxpr(jaxpr).eqns:
        own = str(eqn.source_info.name_stack)
        path = f"{prefix}/{own}" if prefix and own else (prefix or own)
        subs = list(eqn_subjaxprs(eqn))
        if not subs:
            yield path, eqn
        for sub in subs:
            yield from leaf_eqns_with_name_stacks(sub, path)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in ``jaxpr`` and all nested sub-jaxprs."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
    for sub in subjaxprs(jaxpr):
        yield from iter_eqns(sub)


def count_primitives(jaxpr, names: Sequence[str]) -> int:
    """Number of equations (recursively) whose primitive name is in
    ``names``.  A scanned body counts ONCE — this is an equation count,
    not an execution count (scale by trip counts for the latter)."""
    names = frozenset(names)
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name in names)


def count_collectives(jaxpr, names: Optional[Sequence[str]] = None) -> int:
    """Count cross-device collective equations in a (closed) jaxpr.

    The public psum counter the one-collective-per-round invariant tests
    are built on: with the default ``names`` every primitive in
    :data:`COLLECTIVE_PRIMITIVES` counts, so the assertion "exactly one"
    also proves no other collective flavour snuck in.  Pass
    ``names=("psum",)`` to count psums alone.
    """
    return count_primitives(jaxpr, COLLECTIVE_PRIMITIVES
                            if names is None else names)


def scan_bodies(jaxpr) -> List:
    """All ``lax.scan`` body jaxprs in ``jaxpr``, recursively."""
    out = []
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["jaxpr"].jaxpr)
    for sub in subjaxprs(jaxpr):
        out.extend(scan_bodies(sub))
    return out


def _scan_bodies_with_depth(jaxpr, depth=0):
    out = []
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append((depth, eqn.params["jaxpr"].jaxpr))
    for sub in subjaxprs(jaxpr):
        out.extend(_scan_bodies_with_depth(sub, depth + 1))
    return out


def round_body(jaxpr):
    """The K-round loop body of a superstep jaxpr.

    The round scan is the OUTERMOST scan — the one at the shallowest
    sub-jaxpr nesting depth (ties broken by most equations).  Depth, not
    size: the plain superstep's round body (aggregate + sgd step) has
    fewer equations than the per-local-step training scan nested inside
    it.  Returns None when the program has no scan at all (a ``K == 1``
    superstep bypasses ``lax.scan``; its "round body" is the whole
    jaxpr).
    """
    bodies = _scan_bodies_with_depth(jaxpr)
    if not bodies:
        return None
    d_min = min(d for d, _ in bodies)
    return max((b for d, b in bodies if d == d_min),
               key=lambda b: len(b.eqns))


def collect_avals(jaxpr) -> Iterator:
    """Every abstract value flowing through ``jaxpr``: inputs, outputs
    and all intermediate equation operands/results, recursively."""
    jaxpr = _as_jaxpr(jaxpr)
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            yield aval
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield aval
    for sub in subjaxprs(jaxpr):
        yield from collect_avals(sub)


def find_primitives(jaxpr, names: Sequence[str]) -> List:
    """The equations (recursively) whose primitive name is in ``names``."""
    names = frozenset(names)
    return [eqn for eqn in iter_eqns(jaxpr) if eqn.primitive.name in names]


def collective_execution_model(jaxpr, names: Optional[Sequence[str]] = None
                               ) -> Tuple[int, int]:
    """Trip-weighted ``(op_count, payload_bytes)`` of a jaxpr's
    collectives — the quantities the lowered HLO must agree with.

    Each collective equation contributes ``n_operands × trips`` ops and
    ``payload_bytes × trips`` bytes, where ``trips`` is the product of
    the ``length`` params of every enclosing ``lax.scan``: XLA lowers an
    n-ary psum to one all-reduce per operand (modulo combining, which
    the optimized-HLO byte total is invariant to), and a psum inside the
    K-round scan executes K times.  Cross-checked against
    :func:`repro.roofline.hlo.collective_bytes` /
    ``collective_op_counts`` by the analyzer's collective-bytes pass.
    """
    names = frozenset(COLLECTIVE_PRIMITIVES if names is None else names)

    def walk(jx, trips):
        ops = nbytes = 0
        jx = _as_jaxpr(jx)
        for eqn in jx.eqns:
            mult = trips
            if eqn.primitive.name == "scan":
                mult = trips * int(eqn.params["length"])
            if eqn.primitive.name in names:
                for v in eqn.invars:
                    aval = getattr(v, "aval", None)
                    if aval is not None and hasattr(aval, "shape"):
                        n = 1
                        for d in aval.shape:
                            n *= int(d)
                        ops += trips
                        nbytes += n * aval.dtype.itemsize * trips
            is_sub = lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")
            for v in eqn.params.values():
                for j in jax.tree_util.tree_leaves(v, is_leaf=is_sub):
                    inner = (j.jaxpr if hasattr(j, "jaxpr")
                             else (j if hasattr(j, "eqns") else None))
                    if inner is not None:
                        o, b = walk(inner, mult)
                        ops += o
                        nbytes += b
        return ops, nbytes

    return walk(jaxpr, 1)


def psum_payload_bytes(jaxpr, names: Iterable[str] = ("psum",)) -> int:
    """Total bytes of collective OPERANDS in ``jaxpr`` (one trip each).

    For the fused superstep this is the packed flat-buffer size of each
    psum equation — the quantity the collective-bytes pass cross-checks
    against the lowered HLO's all-reduce payloads.
    """
    total = 0
    for eqn in find_primitives(jaxpr, tuple(names)):
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                n = 1
                for d in aval.shape:
                    n *= int(d)
                total += n * aval.dtype.itemsize
    return total
