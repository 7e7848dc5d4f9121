#!/usr/bin/env python3
"""Bring-up check: the federated engine on a TPU at the paper's CNN widths.

Drives the normal entry point, ``repro.fl.api.FederatedTrainer(...).fit``,
on ``make_bundle(CNN_CONFIGS["cnn_mnist"])`` (28x28x1, convs 32/64, FC 512,
10 classes) over the paper's MNIST federation: 100 clients x 600 seeded
synthetic examples under ``artificial_noniid_partition``, 10 clients per
round, batch 50, 12 local steps (one epoch), a 10k-example test set
evaluated every round, 4 rounds in two jitted chunks of 2.

    python chip_smoke.py             # one chip, phases (a)-(d)
    python chip_smoke.py --chips 4   # the sharded engine on four chips

One chip.  Each phase runs with ``impl="pallas"`` and again with
``impl="jnp"``, the plain reference, on the chip:

  (a) fedavg, identity codec — no kernel on the path;
  (a-topk, a-int8) fedavg under the topk + error-feedback and the int8
      uplinks — ``ef_gather`` / ``ef_scatter`` (the device EF table) and
      ``quant_pack`` / ``quant_unpack``, each bit-exact to its oracle;
  (b) fedmmd with the topk + error-feedback uplink — ``gram_sum`` (MK-MMD),
      ``ef_gather`` / ``ef_scatter``;
  (c) fedfusion with the conv operator under the int8 and the int4 uplink —
      ``fusion_conv``, ``quant_pack`` / ``quant_unpack``;
  (d) phase (b) with the cohort-paged host EF store, which must be bitwise
      equal to (b), models and history;
  (codec) the int8 and int4 uplinks as a round runs them, without the
      training around them: every client of a cohort encodes its delta
      of every model leaf (``vmap``), the server decodes and adds the
      clients up — bit-exact;
  (kernels) each main-path kernel alone against its oracle.

Per phase it prints the relative difference of the global-model update
``|theta_pallas - theta_jnp| / |theta_jnp - theta_0|`` against a constant
bound (:data:`UPDATE_BOUNDS`), the per-round losses, the final accuracy and
the compiled superstep's own device memory, checks every loss is finite,
and reads the compiled supersteps: each expected kernel is a
``tpu_custom_call`` of the Pallas run, the jnp run has none, and the
number of convolution / matmul tilings the TPU compiler chose differently
in the two programs is printed (see :data:`UPDATE_BOUNDS`).  A failing
comparison does not stop the later phases; the script fails at the end.

Four chips.  Phase (b) with 12 clients per round over
``make_engine_mesh(4)``, on two engine paths (:data:`SHARD_PATHS`): the
default one (clients vmapped, the eval batch split over the chips) and the
one whose local training is the same program on one chip and on four
(clients one after another, eval replicated).  Each is compared with the
same configuration on one chip in the same process: the replicated model
must be bitwise equal on the four chips, each chip must hold its own
quarter of the chunk, of the row-sharded EF table and (default path) of
the eval batch, the compiled round body must hold the path's all-reduces
and no other collective, and the sharded model must agree with the
one-chip model after one round and after the run within the path's bounds
(:data:`SHARD_BOUNDS`), printed beside two one-chip controls (inputs x
(1 + 2**-23), lr x (1 + 2**-23)).

The script exits non-zero and prints no ok line when no TPU is found
(checked first), when a check fails, when a phase raises, or when a compile
warns that donated buffers were not usable.  Its last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Times printed on the way include compilation and are not benchmarks.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time
import warnings
from unittest import mock

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N_CLIENTS = 100
PER_CLIENT = 600
N_TEST = 10_000
ROUNDS = 4
CHUNK = 2
IMPL, REF = "pallas", "jnp"     # the kernels under test, the plain reference

# Kernel vs oracle on the chip at the paper's widths (phase "kernels").
# quant_pack / quant_unpack / topk_select / ef_gather / ef_scatter must be
# bit-exact.  gram_sum and fusion_conv reorder f32 sums and use the chip's
# own exp: their outputs and the gradients through them (the MK-MMD loss,
# the fusion operator) may differ from the oracle's by a few f32
# roundings (2**-24 ~ 6e-8) grown by the length of the sums.
KERNEL_BOUNDS = {"gram_sum": 1e-6, "mk_mmd2 grad": 1e-5,
                 "fusion_conv": 1e-6, "fusion_conv grad": 1e-6}

# |theta_pallas - theta_jnp| / |theta_jnp - theta_0| after ROUNDS rounds.
# (a) runs no kernel, and every kernel of (a-topk) and (a-int8) is
# bit-exact to its oracle: the two runs agree exactly.  That also needs the
# TPU compiler to tile the local training's convolutions and matmuls
# alike in the two programs, which it decides per program: it does for
# these phases, and it does not for fedavg + int4, whose runs drift apart
# by 0.22-0.35 although every kernel is bit-exact (so int4 is held
# bit-exact in the codec phase instead).  (b), (c) differ by the last-bit float
# differences of gram_sum / fusion_conv and by such tilings; this
# workload turns any last-bit difference into a macroscopic one (max-pool
# ties, top-k selection, stochastic rounding): a one-ulp change of the
# inputs moves the jnp run against itself by up to 0.128 (printed beside
# each of these phases), a tiling change alone (fedavg + int4) by up to
# 0.354, on a v5e over seeds 0-2 (readings in PERF.md).  Their bound sits
# above both.
UPDATE_BOUNDS = {"a": 0.0, "a-topk": 0.0, "a-int8": 0.0,
                 "b": 0.4, "c-int8": 0.4, "c-int4": 0.4}

PHASE_KERNELS = {
    "a": (),
    "a-topk": ("ef_gather", "ef_scatter"),
    "a-int8": ("quant_pack_int8", "quant_unpack_int8"),
    "b": ("gram_sum", "ef_gather", "ef_scatter"),
    "c-int8": ("fusion_conv", "quant_pack_int8", "quant_unpack_int8"),
    "c-int4": ("fusion_conv", "quant_pack_int4", "quant_unpack_int4"),
}

# The four-chip engine paths: the default (clients vmapped, the eval batch
# split over the chips, one masked-sum psum for its metrics) and the one
# whose local training is the same program on one chip and on four
# (clients one after another, eval replicated).  The round body holds the
# aggregation's all-reduce plus, with the eval split, the eval's.
SHARD_PATHS = {
    "default": dict(mode="client_parallel", sharded_eval=True),
    "sequential": dict(mode="client_sequential", sharded_eval=False),
}

# Sharded vs one chip, |theta_4 - theta_1| / |theta_1 - theta_0|, after one
# round and after ROUNDS rounds.  Sequential: after one round only the
# order of the aggregation sum differs (f32 rounding, ~1e-7); after the
# run the drift is bounded just above the largest one-chip one-ulp control
# of this path over seeds 0-2 on a v5e (0.077).  Default: the four chips
# vmap 3 clients each and one chip vmaps 12, so local training is not the
# same program and the compiler may tile it differently; both readings
# are bounded above the largest drift either cause gave on one v5e over
# seeds 0-2: one-ulp controls of this path 0.081, a tiling change alone
# 0.354 (see UPDATE_BOUNDS).  The controls are printed beside each
# reading; their readings over seeds are in PERF.md.
SHARD_BOUNDS = {"sequential": (1e-4, 0.08), "default": (0.4, 0.4)}


class CheckFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise CheckFailed(msg)


def require_tpu(n_chips):
    """The accelerator check, made before anything of the repo loads."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX sees {devs[0].platform} "
                         f"devices only")
    if len(devs) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} TPU chips, "
                         f"JAX sees {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# the workload
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Workload:
    """Everything a phase run needs; the defaults are the paper's MNIST."""
    cnn: object
    n_clients: int = N_CLIENTS
    per_client: int = PER_CLIENT
    n_test: int = N_TEST
    cohort: int = 10
    batch: int = 50
    local_steps: int = 12
    lr: float = 0.06
    rounds: int = ROUNDS
    chunk: int = CHUNK
    seed: int = 0


_ARRAYS = {}


def make_data(w: Workload, *, scale_inputs=1.0):
    """Seeded synthetic MNIST, dealt non-IID (2 label shards per client).

    A fresh dataset per run (sampling advances its generator) over arrays
    made once per (workload, input scale)."""
    from repro.data.federated import FederatedDataset
    from repro.data.partition import artificial_noniid_partition
    from repro.data.synth import class_images
    key = (w, scale_inputs)
    if key not in _ARRAYS:
        shape = tuple(w.cnn.input_shape)
        x, y = class_images(w.n_clients * w.per_client // 10, n_classes=10,
                            shape=shape, seed=w.seed, noise=0.2,
                            template_seed=0)
        xt, yt = class_images(w.n_test // 10, n_classes=10, shape=shape,
                              seed=w.seed + 1, noise=0.2, template_seed=0)
        if scale_inputs != 1.0:
            x = (x * np.float32(scale_inputs)).astype(np.float32)
        parts = artificial_noniid_partition(x, y, w.n_clients,
                                            shards_per_client=2,
                                            seed=w.seed)
        _ARRAYS[key] = (parts, {"x": xt, "y": yt})
    parts, test = _ARRAYS[key]
    return FederatedDataset(parts, test, seed=w.seed)


def make_fl(w: Workload, phase: str, **over):
    from repro.configs.base import FLConfig
    base = dict(clients_per_round=w.cohort, local_batch=w.batch,
                local_steps=w.local_steps, lr=w.lr)
    per_phase = {
        "a": dict(algorithm="fedavg"),
        "a-topk": dict(algorithm="fedavg", uplink_codec="topk"),
        "a-int8": dict(algorithm="fedavg", uplink_codec="int8"),
        "b": dict(algorithm="fedmmd", uplink_codec="topk"),
        "c-int8": dict(algorithm="fedfusion", fusion_op="conv",
                       uplink_codec="int8"),
        "c-int4": dict(algorithm="fedfusion", fusion_op="conv",
                       uplink_codec="int4"),
    }
    return FLConfig(**{**base, **per_phase[phase], **over})


class SuperstepSpy:
    """Compiles the supersteps the engine jits ahead of time, so the
    executable that runs is the one whose HLO is read.

    While :meth:`watching`, ``jax.jit`` of a function named ``superstep``
    returns a caller that lowers and compiles per argument signature and
    keeps, for each signature, the compiled executable, the abstract
    arguments, the placement of the chunk images, the EF table and the
    eval batch, and each chip's bytes in use once its first call is
    dispatched (its arguments and outputs allocated)."""

    def __init__(self):
        self.steps = []

    @contextlib.contextmanager
    def watching(self):
        import jax
        real_jit = jax.jit

        def jit(fn, *args, **kwargs):
            jitted = real_jit(fn, *args, **kwargs)
            if getattr(fn, "__name__", "") != "superstep":
                return jitted
            by_sig = {}

            def call(*call_args):
                leaves, tree = jax.tree.flatten(call_args)
                sig = (tree, tuple((x.shape, x.dtype, x.sharding)
                                   for x in leaves))
                if sig not in by_sig:
                    # uncommitted arrays (a fresh jnp value) follow the
                    # committed ones, as they do in a jit dispatch
                    abstract = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype,
                            sharding=x.sharding if x.committed else None),
                        call_args)
                    compiled = jitted.lower(*abstract).compile()
                    by_sig[sig] = compiled
                    step = {"jitted": jitted, "abstract": abstract,
                            "compiled": compiled,
                            "placement": _placements(call_args)}
                    out = compiled(*call_args)
                    step["in_use"] = {d.id: bytes_in_use(d)
                                      for d in jax.devices()}
                    self.steps.append(step)
                    return out
                return by_sig[sig](*call_args)

            return call

        with mock.patch.object(jax, "jit", jit):
            yield self


def _placements(call_args):
    """Device ids and shard shapes of the chunk images, of the eval batch's
    images when the eval runs in the superstep (its last two arguments,
    batch and mask) and, for a compressed superstep ``(state, ef, mirror,
    batches, ...)`` with error feedback, of the largest EF-table leaf."""
    import jax
    if isinstance(call_args[1], dict) and "x" in call_args[1]:
        out = {"x": call_args[1]["x"]}
    else:
        out = {"x": call_args[3]["x"]}
        ef = jax.tree.leaves(call_args[1])
        if ef:                          # stateless codecs carry no EF
            out["ef"] = max(ef, key=lambda a: a.size)
    if isinstance(call_args[-2], dict) and "x" in call_args[-2]:
        out["eval x"] = call_args[-2]["x"]
    return {k: [(s.device.id, tuple(s.data.shape))
                for s in v.addressable_shards] for k, v in out.items()}


def tilings(run):
    """The convolution / matmul tilings (window configurations) the TPU
    compiler chose in a run's compiled supersteps."""
    import collections
    import re
    out = collections.Counter()
    for step in run["steps"]:
        for line in step["compiled"].as_text().splitlines():
            if "kind=kOutput" in line or " convolution(" in line:
                win = re.search(r'"window_config":(\{[^}]*\})', line)
                shape = re.sub(r"S\(\d\)", "", line.split("=", 1)[1].split()[0])
                out[(shape, win.group(1) if win else "")] += 1
    return out


def tpu_kernels(hlo_text):
    """Names of the ``tpu_custom_call`` instructions of an HLO module."""
    names = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.append(line.split("=", 1)[0].strip().lstrip("%"))
    return names


def run_fit(w: Workload, fl, *, impl, data=None, ef_store="device",
            mesh=None, sharded_eval=True, rounds=None,
            mode="client_parallel", label=""):
    """One ``FederatedTrainer.fit``: its result and compiled supersteps.

    Fails when a compile warned that donated buffers were not usable, and
    prints the wall time and each compiled superstep's own device memory."""
    import jax

    from repro.fl.api import (EngineOptions, EvalOptions, FederatedTrainer,
                              RunOptions)
    from repro.models.registry import make_bundle
    opts = RunOptions(
        mode=mode, seed=w.seed, eval=EvalOptions(every=1, examples=w.n_test),
        engine=EngineOptions(superstep_rounds=w.chunk, impl=impl,
                             ef_store=ef_store, mesh=mesh,
                             sharded_eval=sharded_eval))
    bundle = make_bundle(w.cnn)
    trainer = FederatedTrainer(bundle, fl, data or make_data(w), opts)
    spy = SuperstepSpy()
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught, spy.watching():
        warnings.simplefilter("always")
        res = trainer.fit(rounds or w.rounds)
        jax.block_until_ready(res.global_state)
    wall = time.perf_counter() - t0
    donation = [str(c.message) for c in caught
                if "donated buffers were not usable" in str(c.message)]
    check(not donation, f"{label}: a compile warned that donated buffers "
          f"were not usable: {donation[:1]}")
    print(f"  {label}: wall {wall:.3f} s (compilation included); "
          f"superstep memory per chip {[step_memory(s) for s in spy.steps]}")
    return {"result": res, "steps": spy.steps, "bundle": bundle}


def step_memory(step):
    """A compiled superstep's own device memory per chip, from the
    compiler: arguments, outputs, temporaries and the output bytes that
    alias a donated argument."""
    m = step["compiled"].memory_analysis()
    return {k: getattr(m, f"{k}_size_in_bytes")
            for k in ("argument", "output", "temp", "alias")}


def bytes_in_use(device):
    return (device.memory_stats() or {}).get("bytes_in_use", 0)


def peak_bytes(device):
    """The device's peak bytes in use since the process started."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use", 0)


def init_state(w: Workload, bundle, fl):
    import jax

    from repro.core.rounds import init_global_state
    return init_global_state(bundle, fl, jax.random.PRNGKey(w.seed))


def rel_update_diff(a, b, theta0):
    """|a - b| / |b - theta0| over every leaf of two global states."""
    import jax
    num = den = 0.0
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(theta0)):
        x, y, z = (np.asarray(v, np.float64) for v in (x, y, z))
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum((y - z) ** 2))
    return float(np.sqrt(num) / np.sqrt(den))


def history_field(res, key):
    return [h[key] for h in res.comm.history]


def bitwise_equal(a, b):
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def check_losses(label, res):
    for key in ("loss", "local_loss"):
        vals = history_field(res, key)
        check(len(vals) > 0 and all(np.isfinite(v) for v in vals),
              f"{label}: non-finite {key} {vals}")


def check_kernels(label, run, expected):
    """Each compiled superstep of a Pallas run holds every expected
    kernel as a ``tpu_custom_call``; a jnp run holds none."""
    check(run["steps"], f"{label}: no superstep was compiled")
    for step in run["steps"]:
        names = tpu_kernels(step["compiled"].as_text())
        if expected is None:
            check(not names, f"{label}: the jnp reference compiled "
                  f"tpu_custom_call kernels {names}")
            continue
        missing = [k for k in expected if not any(k in n for n in names)]
        check(not missing, f"{label}: kernels {missing} are not "
              f"tpu_custom_calls of the compiled superstep (found {names})")
    return sorted({n for s in run["steps"]
                   for n in tpu_kernels(s["compiled"].as_text())})


# ---------------------------------------------------------------------------
# one chip: phases (a)-(d)
# ---------------------------------------------------------------------------

def kernels_phase():
    """Each main-path kernel against its oracle at the paper's widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.mk_mmd import gram_sum
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    n = 7 * 7 * 64 * 512                     # the FC weight, 1,605,632
    x = jax.random.normal(k[0], (n,)) * 1e-3
    u = jax.random.uniform(k[1], (n,))

    def both(fn, *args):
        return [jax.jit(functools.partial(fn, impl=i))(*args)
                for i in (IMPL, REF)]

    for bits in (8, 4):
        scale = jnp.max(jnp.abs(x)) / (127 if bits == 8 else 7)
        got, want = both(lambda a, b, impl: ops.quantize_pack(
            a, scale, b, bits=bits, impl=impl), x, u)
        diff = int(jnp.sum(got != want))
        print(f"  quant_pack int{bits}: {diff} of {want.size} codes differ")
        check(diff == 0, f"quant_pack int{bits} is not bit-exact")
        got, want = both(lambda q, impl: ops.quantize_unpack(
            q, scale, bits=bits, n=n, impl=impl), want)
        check(bool(jnp.array_equal(got, want)),
              f"quant_unpack int{bits} is not bit-exact")
    thresh = jnp.sort(jnp.abs(x))[-n // 20]
    got, want = both(lambda a, impl: ops.topk_threshold_select(
        a, thresh, impl=impl), x)
    check(bool(jnp.array_equal(got, want)), "topk_select is not bit-exact")
    table = jax.random.normal(k[2], (N_CLIENTS, n))
    idx = jax.random.permutation(k[3], N_CLIENTS)[:10].astype(jnp.int32)
    rows = jax.random.normal(k[4], (10, n))
    got, want = both(lambda t, i, impl: ops.ef_gather(t, i, impl=impl),
                     table, idx)
    check(bool(jnp.array_equal(got, want)), "ef_gather is not bit-exact")
    got, want = both(lambda t, i, r, impl: ops.ef_scatter(t, i, r,
                                                          impl=impl),
                     table, idx, rows)
    check(bool(jnp.array_equal(got, want)), "ef_scatter is not bit-exact")
    print(f"  quant_unpack int8/int4, topk_select, ef_gather / ef_scatter "
          f"on [{N_CLIENTS}, {n}]: bit-exact")
    # the default dispatch, impl="auto", must take the compiled kernel
    auto = tpu_kernels(jax.jit(lambda t, i: ops.ef_gather(t, i)).lower(
        table, idx).compile().as_text())
    print(f"  impl='auto' ef_gather: tpu_custom_call kernels {auto}")
    check(any("ef_gather" in name for name in auto),
          "impl='auto' did not compile the Pallas ef_gather kernel")

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def bounded(key, err):
        print(f"  {key}: relative error {err!r} "
              f"(bound {KERNEL_BOUNDS[key]!r})")
        check(err <= KERNEL_BOUNDS[key], f"{key} error {err!r}")

    def grads(fn, args, argnums):
        return both(lambda *a, impl: jax.grad(
            functools.partial(fn, impl=impl), argnums)(*a), *args)

    widths = (1.0, 2.0, 4.0, 8.0, 16.0)     # FLConfig.mmd_widths
    fx = jnp.abs(jax.random.normal(k[5], (50, 64)))   # pooled, batch 50
    fy = 1.1 * fx[::-1]
    sigma = jnp.mean(ref.sq_dists(fx, fy))
    got = jax.jit(functools.partial(gram_sum, widths=widths))(fx, fy, sigma)
    bounded("gram_sum", rel(got, ref.gram_sum_ref(fx, fy, sigma, widths)))
    got, want = grads(lambda a, b, impl: ops.mk_mmd2(a, b, widths,
                                                     impl=impl),
                      (fx, fy), (0, 1))
    bounded("mk_mmd2 grad", max(rel(a, b) for a, b in zip(got, want)))

    fg = jax.random.normal(k[6], (50, 7, 7, 64))
    fl = 0.5 * fg + 0.1
    w = jax.random.normal(k[7], (128, 64)) / np.sqrt(128)
    got, want = both(lambda a, b, c, impl: ops.fused_fusion_conv(
        a, b, c, impl=impl), fg, fl, w)
    bounded("fusion_conv", rel(got, want))
    got, want = grads(lambda a, b, c, impl: jnp.sum(
        ops.fused_fusion_conv(a, b, c, impl=impl) ** 2), (fg, fl, w),
        (0, 1, 2))
    bounded("fusion_conv grad", max(rel(a, b) for a, b in zip(got, want)))


def one_chip(w: Workload):
    """Phases (a)-(d), the codec, then the kernels alone (last, so that
    their full-size test tables leave no mark on the phases' device
    memory).  A comparison beyond its bound is reported and the later
    phases still run; the run fails at the end."""
    import jax
    failed = []
    b_pallas = None
    for phase, bound in UPDATE_BOUNDS.items():
        fl = make_fl(w, phase)
        t0 = time.perf_counter()
        got = run_fit(w, fl, impl=IMPL, label=f"{phase} {IMPL}")
        want = run_fit(w, fl, impl=REF, label=f"{phase} {REF}")
        theta0 = init_state(w, got["bundle"], fl)
        gr, wr = got["result"], want["result"]
        check_losses(f"{phase} {IMPL}", gr)
        check_losses(f"{phase} {REF}", wr)
        rel = rel_update_diff(gr.global_state, wr.global_state, theta0)
        kernels = check_kernels(f"{phase} {IMPL}", got, PHASE_KERNELS[phase])
        check_kernels(f"{phase} {REF}", want, None)
        retiled = sum((tilings(got) - tilings(want)).values())
        control = ""
        if bound:
            ctl = run_fit(w, fl, impl=REF,
                          data=make_data(w, scale_inputs=1.0 + 2.0 ** -23),
                          label=f"{phase} {REF}, inputs x (1+2**-23)")
            control = (f"; {REF} with inputs x (1+2**-23) vs {REF}: "
                       + repr(rel_update_diff(ctl["result"].global_state,
                                              wr.global_state, theta0)))
        print(f"  {phase}: |{IMPL} - {REF}| / |update| = {rel!r} "
              f"(bound {bound!r}){control}")
        print(f"  {phase}: convolution / matmul tilings the compiler chose "
              f"differently in the {IMPL} program: {retiled}")
        print(f"  {phase}: eval loss per round {IMPL} "
              f"{history_field(gr, 'loss')} {REF} "
              f"{history_field(wr, 'loss')}")
        print(f"  {phase}: local loss per round {IMPL} "
              f"{history_field(gr, 'local_loss')} {REF} "
              f"{history_field(wr, 'local_loss')}")
        print(f"  {phase}: final acc {IMPL} {history_field(gr, 'acc')[-1]!r} "
              f"{REF} {history_field(wr, 'acc')[-1]!r}")
        print(f"  {phase}: tpu_custom_call kernels {kernels}")
        if rel <= bound:
            print(f"phase {phase}: ok ({time.perf_counter() - t0:.3f} s)")
        else:
            failed.append(f"{phase}: |{IMPL} - {REF}| / |update| = {rel!r} "
                          f"exceeds the bound {bound!r} ({retiled} tilings "
                          f"differ)")
            print(f"phase {phase}: FAILED")
        if phase == "b":
            b_pallas = gr

    # (d): phase (b) on the cohort-paged host EF store
    t0 = time.perf_counter()
    fl = make_fl(w, "b")
    paged = run_fit(w, fl, impl=IMPL, ef_store="host", label=f"d {IMPL}")
    pr = paged["result"]
    check(pr.stats.get("ef_store") == "host",
          f"d: the run used ef_store {pr.stats.get('ef_store')!r}")
    check_kernels(f"d {IMPL}", paged, PHASE_KERNELS["b"])
    same_model = bitwise_equal(pr.global_state, b_pallas.global_state)
    same_history = pr.comm.history == b_pallas.comm.history
    print(f"  d vs b: model bitwise equal {same_model}, history equal "
          f"{same_history}")
    if same_model and same_history:
        print(f"phase d: ok ({time.perf_counter() - t0:.3f} s)")
    else:
        failed.append("d: the host EF store is not bitwise equal to the "
                      "device table")
        print("phase d: FAILED")
    print(f"device peak_bytes_in_use over phases (a)-(d): "
          f"{peak_bytes(jax.devices()[0])}")

    for name, run in (("codec", lambda: codec_phase(w)),
                      ("kernels", kernels_phase)):
        t0 = time.perf_counter()
        try:
            run()
            print(f"phase {name}: ok ({time.perf_counter() - t0:.3f} s)")
        except CheckFailed as e:
            failed.append(str(e))
            print(f"phase {name}: FAILED")
    check(not failed, "; ".join(failed))


def codec_phase(w: Workload):
    """The int8 / int4 uplink as the round runs it, with no training around
    it: each client of a cohort encodes a delta of every leaf of the model
    (``vmap``), the server decodes the payloads and adds the clients up.
    The Pallas and jnp programs must agree bit for bit: codes, scales,
    decoded deltas and their sum."""
    import jax
    import jax.numpy as jnp

    from repro.compress import make_codec
    from repro.core.aggregate import client_weighted_sum, normalize_weights
    from repro.models.registry import make_bundle
    model = init_state(w, make_bundle(w.cnn), make_fl(w, "a"))["model"]
    leaves, tree = jax.tree.flatten(model)
    k = jax.random.split(jax.random.PRNGKey(w.seed), len(leaves) + 1)
    local = jax.tree.unflatten(tree, [
        x + 1e-3 * jax.random.normal(kk, (w.cohort,) + x.shape)
        for x, kk in zip(leaves, k[1:])])
    keys = jax.random.split(k[0], w.cohort)
    weights = normalize_weights(jnp.arange(1.0, w.cohort + 1.0))
    for bits in (8, 4):
        outs, names = {}, {}
        for impl in (IMPL, REF):
            codec = make_codec(f"int{bits}", impl=impl).bind(model)

            def one_round(model, local, keys):
                def client(mine, key):
                    delta = jax.tree.map(jnp.subtract, mine, model)
                    payload, _ = codec.encode(delta, codec.init_state(), key)
                    return payload, codec.decode(payload)
                payload, decoded = jax.vmap(client)(local, keys)
                return payload, client_weighted_sum(weights, decoded)

            compiled = jax.jit(one_round).lower(model, local,
                                                keys).compile()
            names[impl] = tpu_kernels(compiled.as_text())
            outs[impl] = compiled(model, local, keys)
        same = bitwise_equal(outs[IMPL], outs[REF])
        print(f"  codec int{bits}, {w.cohort} clients x every model leaf: "
              f"{IMPL} vs {REF} bitwise equal {same}; tpu_custom_call "
              f"kernels {sorted(names[IMPL])}")
        for kind in ("pack", "unpack"):
            check(any(f"quant_{kind}_int{bits}" in n for n in names[IMPL]),
                  f"codec int{bits}: no quant_{kind} kernel was compiled")
        check(not names[REF], f"codec int{bits}: the jnp reference "
              f"compiled tpu_custom_call kernels {names[REF]}")
        check(same, f"codec int{bits}: {IMPL} and {REF} differ")


# ---------------------------------------------------------------------------
# four chips: the sharded engine against one chip
# ---------------------------------------------------------------------------

def chunk_collectives(step):
    """(collectives in the traced round body, collectives outside the
    round scan — the per-chunk prologue psum that seeds round 0's EF rows
    and weight total — and the compiled module's trip-weighted counts
    per kind)."""
    from repro.analysis import count_collectives, round_body
    from repro.roofline.hlo import collective_summary
    jaxpr = step["jitted"].trace(*step["abstract"]).jaxpr
    n_body = count_collectives(round_body(jaxpr))
    n_outside = count_collectives(jaxpr) - n_body
    compiled = {k: n for k, (n, _) in collective_summary(
        step["compiled"].as_text()).items() if n}
    return n_body, n_outside, compiled


def four_chips(w: Workload, seeds, n_shards=4):
    """Phase (b) sharded over ``make_engine_mesh(n_shards)`` vs one chip,
    on each engine path of :data:`SHARD_PATHS`.

    The structure (replicas, shards, collectives) is checked as it is
    read; the agreement readings of every path and seed are printed first
    and checked at the end."""
    import jax

    from repro.launch.mesh import make_engine_mesh
    mesh = make_engine_mesh(n_shards)
    eps = 2.0 ** -23
    late = []
    for path, opts in SHARD_PATHS.items():
        fit = functools.partial(run_fit, impl=IMPL, **opts)
        n_eval = int(opts["sharded_eval"])
        for seed in seeds:
            ws = dataclasses.replace(w, seed=seed)
            fl = make_fl(ws, "b")
            t0 = time.perf_counter()
            tag = f"{path} seed {seed}"
            shd = fit(ws, fl, mesh=mesh,
                      label=f"{tag} sharded on {n_shards} chips")
            one = fit(ws, fl, label=f"{tag} one chip")
            sr, orr = shd["result"], one["result"]
            check_losses(f"{tag} sharded", sr)
            check_losses(f"{tag} one chip", orr)
            theta0 = init_state(ws, one["bundle"], fl)
            check(sr.stats.get("client_shards") == n_shards,
                  f"{tag}: client_shards {sr.stats.get('client_shards')}")
            check(sr.stats.get("sharded_eval") == opts["sharded_eval"],
                  f"{tag}: sharded_eval {sr.stats.get('sharded_eval')}")

            # the replicated model: bitwise equal on every chip
            for leaf in jax.tree.leaves(sr.global_state):
                shards = leaf.addressable_shards
                check(len({s.device.id for s in shards}) == n_shards,
                      f"{tag}: model leaf on devices "
                      f"{[s.device.id for s in shards]}")
                first = np.asarray(shards[0].data)
                check(all(np.array_equal(first, np.asarray(s.data))
                          for s in shards[1:]),
                      f"{tag}: the model's copies on the chips differ")
            print(f"  {tag}: the model's copies on the {n_shards} chips: "
                  f"bitwise equal")

            # each chip holds its own quarter of the chunk, of the EF table
            # and, with the eval split, of the eval batch (else a full
            # copy), and has them in memory
            for step in shd["steps"]:
                ef_leaf = max(jax.tree.leaves(step["abstract"][1]),
                              key=lambda a: a.size)
                full = {"x": step["abstract"][3]["x"].shape,
                        "ef": ef_leaf.shape,
                        "eval x": step["abstract"][-2]["x"].shape}
                axis = {"x": 1, "ef": 0, "eval x": 0 if n_eval else None}
                check(set(step["placement"]) == set(full),
                      f"{tag}: superstep arguments {set(step['placement'])}")
                for name, place in step["placement"].items():
                    devices = [d for d, _ in place]
                    shapes = {sh for _, sh in place}
                    want, ax = list(full[name]), axis[name]
                    if ax is not None:
                        want[ax] //= n_shards
                    check(len(set(devices)) == n_shards
                          and shapes == {tuple(want)},
                          f"{tag}: {name} shards {place}, want "
                          f"{tuple(want)} on each chip of {full[name]}")
                ef_shard = (int(np.prod(step["placement"]["ef"][0][1]))
                            * ef_leaf.dtype.itemsize)
                print(f"  {tag}: superstep shards {step['placement']}; "
                      f"bytes in use per chip at its first dispatch "
                      f"{step['in_use']}")
                for d in mesh.devices.flat:
                    check(step["in_use"][d.id] >= ef_shard,
                          f"{tag}: chip {d.id} has {step['in_use'][d.id]} "
                          f"bytes in use, less than its {ef_shard}-byte "
                          f"EF shard")

            # per round: the aggregation's all-reduce plus the split eval's;
            # the compiled chunk of k rounds holds k times those plus the
            # prologue's, and no collective of another kind
            for step in shd["steps"]:
                n_body, n_outside, compiled = chunk_collectives(step)
                k = step["abstract"][5].shape[0]
                print(f"  {tag}: traced {n_body} collectives in the round "
                      f"body, {n_outside} before the round scan; compiled "
                      f"chunk of {k} rounds (trip-weighted): {compiled}")
                check(n_body == 1 + n_eval,
                      f"{tag}: round body has {n_body} collectives")
                check(compiled == {"all-reduce": k * n_body + n_outside},
                      f"{tag}: compiled collectives {compiled} for {k} "
                      f"rounds")

            # agreement with one chip after one round and after the run
            shd1 = fit(ws, fl, mesh=mesh, rounds=1,
                       label=f"{tag} sharded, 1 round")
            one1 = fit(ws, fl, rounds=1, label=f"{tag} one chip, 1 round")
            rel1 = rel_update_diff(shd1["result"].global_state,
                                   one1["result"].global_state, theta0)
            rel = rel_update_diff(sr.global_state, orr.global_state, theta0)
            controls = {}
            for name, fl_c, scale in (
                    ("inputs", fl, 1.0 + eps),
                    ("lr", dataclasses.replace(fl, lr=fl.lr * (1 + eps)),
                     1.0)):
                for rounds in (1, ws.rounds):
                    ctl = fit(ws, fl_c, rounds=rounds,
                              data=make_data(ws, scale_inputs=scale),
                              label=f"{tag} one chip, {name} x (1+2**-23), "
                                    f"{rounds} rounds")
                    ref = one1 if rounds == 1 else one
                    controls[f"{name}, {rounds} rounds"] = rel_update_diff(
                        ctl["result"].global_state,
                        ref["result"].global_state, theta0)
            b1, b_run = SHARD_BOUNDS[path]
            print(f"  {tag}: sharded vs one chip after 1 round {rel1!r} "
                  f"(bound {b1!r}), after {ws.rounds} rounds {rel!r} "
                  f"(bound {b_run!r}); one-chip controls {controls}")
            print(f"  {tag}: eval loss per round sharded "
                  f"{history_field(sr, 'loss')} one chip "
                  f"{history_field(orr, 'loss')}")
            late += [(rel1 <= b1, f"{tag}: sharded vs one chip after 1 "
                                  f"round {rel1!r} exceeds {b1!r}"),
                     (rel <= b_run, f"{tag}: sharded vs one chip after the "
                                    f"run {rel!r} exceeds {b_run!r}")]
            print(f"{tag}: structure ok ({time.perf_counter() - t0:.3f} s)")
    for ok, msg in late:
        check(ok, msg)
    print("sharded vs one chip: every reading within its bound")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(d) on one chip; 4: the sharded "
                         "engine on four chips against one chip")
    ap.add_argument("--seeds", default="0",
                    help="--chips 4 only: comma-separated seeds")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import CNN_CONFIGS
    from repro.launch.compile_cache import enable_compile_cache
    print(f"platform {devs[0].platform}, device_kind {devs[0].device_kind}, "
          f"devices {len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(Workload(CNN_CONFIGS["cnn_mnist"]))
        else:
            four_chips(Workload(CNN_CONFIGS["cnn_mnist"], cohort=12),
                       [int(s) for s in args.seeds.split(",")])
    except CheckFailed as e:
        raise SystemExit(f"FAIL: {e}") from None
    print(f"all phases ok in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
