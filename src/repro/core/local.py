"""On-device (client-side) training — the paper's additional mechanisms.

A client receives the global model, builds its *trainable* state (local
model copy + whatever extra state the algorithm plugin carries — the
fusion module for FedFusion), and runs ``fl.local_steps`` SGD steps with
the algorithm's objective.  The objective itself lives in the
:class:`repro.fl.api.Algorithm` plugin (``local_loss`` hook); this module
supplies the mechanism-independent machinery: the optimizer loop, the
epoch/step ``lax.scan`` nesting, and the paper-§3.3 frozen-stream feature
cache that two-stream algorithms (FedMMD, FedFusion) opt into via
``Algorithm.two_stream``.

The frozen global stream is closed over and NEVER updated during local
training (paper Fig. 1: "the global model is fixed while the local model
is trained through back propagation").
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.models.registry import ModelBundle
from repro.obs.scopes import LOCAL_TRAIN, in_part
from repro.optim import make_optimizer


def _algorithm(fl: FLConfig):
    # lazy: repro.fl.api sits above repro.core in the package graph
    # (repro.fl/__init__ pulls in modules that import repro.core), so the
    # plugin is resolved at factory-call time, never at module import.
    from repro.fl.api import make_algorithm
    return make_algorithm(fl.algorithm)


def make_local_loss(bundle: ModelBundle, fl: FLConfig, *, impl="auto"):
    algo = _algorithm(fl)

    def loss_fn(trainable, global_model, batch, cached_feats_g=None):
        """``cached_feats_g``: precomputed frozen-stream features for this
        batch (paper §3.3 — E_g's maps can be recorded once per round);
        None recomputes them (the E=1 / uncached path)."""
        return algo.local_loss(bundle, fl, trainable, global_model, batch,
                               cached_feats_g, impl=impl)

    return loss_fn


def make_local_trainer(bundle: ModelBundle, fl: FLConfig, *, impl="auto"):
    """Returns local_train(global_model, global_extra, batches, lr) ->
    (trainable, mean_loss).

    ``global_extra`` is the algorithm's extra global state
    (``Algorithm.extra_from_state`` — the fusion params for FedFusion,
    None for single-stream algorithms).
    ``batches``: pytree whose leaves have leading dim ``fl.local_steps``
    (one local SGD step per slice).
    """
    algo = _algorithm(fl)
    opt_init, opt_update = make_optimizer(fl.optimizer, fl.momentum)
    loss_fn = make_local_loss(bundle, fl, impl=impl)

    cache = (fl.cache_global_features and algo.two_stream
             and fl.local_epochs > 1)

    @in_part(LOCAL_TRAIN)
    def local_train(global_model, global_extra, batches, lr):
        trainable: Dict[str, Any] = algo.init_trainable(fl, global_model,
                                                        global_extra)
        state = opt_init(trainable)

        cached = None
        if cache:
            # paper §3.3: the frozen E_g features for the round's batches
            # are computed ONCE and reused across the E local epochs —
            # saves (E-1) global-stream forwards per client per round.
            def extract_one(_, batch):
                f, _aux = bundle.extract(
                    jax.lax.stop_gradient(global_model), batch)
                return None, jax.lax.stop_gradient(f)

            _, cached = jax.lax.scan(extract_one, None, batches)

        def step_cached(carry, xs):
            batch, feats_g = xs
            tr, st = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                tr, global_model, batch, feats_g)
            tr, st = opt_update(tr, grads, st, lr)
            return (tr, st), loss

        def step_plain(carry, batch):
            tr, st = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                tr, global_model, batch)
            tr, st = opt_update(tr, grads, st, lr)
            return (tr, st), loss

        def epoch(carry, _):
            if cache:
                return jax.lax.scan(step_cached, carry, (batches, cached))
            return jax.lax.scan(step_plain, carry, batches)

        if fl.local_epochs > 1:
            (trainable, _), losses = jax.lax.scan(
                epoch, (trainable, state), None, length=fl.local_epochs)
        else:
            (trainable, _), losses = jax.lax.scan(
                step_plain, (trainable, state), batches)
        return trainable, jnp.mean(losses)

    return local_train
