"""Device scopes: the four parts of a federated round, named in the trace.

Every operation of a round's traced program sits under one of four fixed
``jax.named_scope`` names, so a profiler trace attributes each device op
(its ``tf_op`` name stack, e.g. ``jit(superstep)/while/body/closed_call/
vmap(local_train)/transpose(jvp())/conv_general_dilated``) to the part of
the round that ran it:

* ``local_train`` — a client's forward, backward, the algorithm's term
  (FedMMD's ``gram_sum`` kernel, FedFusion's operator) and the optimizer
  step (:func:`repro.core.local.make_local_trainer`);
* ``codec`` — downlink and uplink encode / decode (top-k, quantisation),
  the codecs' key split, and the EF row gather / scatter with their
  exchanges (``repro.core.rounds``, ``repro.engine.superstep``);
* ``aggregate`` — the example weights, the weighted client sums, the
  psum, the server's finish and apply, the round metrics, telemetry taps
  and the controller (``repro.core.aggregate`` and the round factories);
* ``eval`` — the test-set forward, in the round scan and in ``jit_eval``
  alike (:func:`repro.engine.evaljit.make_eval_fn`).

Outside them stays only plumbing: the round scan's loop, its per-round
slices of the staged chunk and the stacking of the round metrics, and
vmap moving an unbatched output onto the client axis.  The round key's
``fold_in`` feeds the codecs alone and lies under ``codec``.

The outermost part names an op: :func:`part` entered while another part
is open adds no scope, so no op carries two of the four names (the
sharded evaluator's psum stays ``eval``; an aggregation helper called
from inside a part stays that part's).  The scopes change op metadata
only, never the computation.  The analyzer's ``scopes`` pass holds the
coverage at every config point of its matrix.

JAX's persistent compile cache leaves op metadata out of its key by
default, so a program that differs from a cached one only in these names
would load the cached executable and profile under its names; importing
this module keeps the metadata in the key.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax

__all__ = ["LOCAL_TRAIN", "CODEC", "AGGREGATE", "EVAL", "PARTS", "part",
           "in_part"]

LOCAL_TRAIN = "local_train"
CODEC = "codec"
AGGREGATE = "aggregate"
EVAL = "eval"
PARTS = (LOCAL_TRAIN, CODEC, AGGREGATE, EVAL)

# a profile's scopes are this program's (see the module docstring)
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# the part open on this thread while a round is being traced (tracing is
# synchronous, so a thread-local is the trace's own nesting)
_open = threading.local()


@contextlib.contextmanager
def part(name: str):
    """``with part(CODEC): ...`` — trace the block under the part's scope,
    unless a part is already open (the outermost one names the ops)."""
    if name not in PARTS:
        raise ValueError(f"unknown round part {name!r}; parts: {PARTS}")
    if getattr(_open, "name", None) is not None:
        yield
        return
    _open.name = name
    try:
        with jax.named_scope(name):
            yield
    finally:
        _open.name = None


def in_part(name: str):
    """Decorator form of :func:`part`."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with part(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap
