"""Jit-able global-model evaluation with fixed (pad-and-mask) shapes.

The pre-engine evaluator ran ``bundle.apply`` uncompiled on the raw test
batch every ``eval_every`` rounds — op-by-op Python dispatch on what the
paper plots every single round (Fig. 4-7 are accuracy-per-round curves).
Here the metrics are a traceable function of ``(global_state, batch,
mask)`` so they can be jitted standalone, or folded straight into the
superstep's ``lax.scan`` body when evaluation happens every round.

Shapes are stabilised by padding the test batch to a power-of-two bucket
(capped at ``max_examples``) with a per-example validity mask: one
compiled evaluator serves any test-set size, and the masked means are
numerically identical to the unpadded ones (pad rows carry zero weight,
the divisor is the true example count).

Sharded evaluation (``make_eval_fn(shard=)`` + ``pad_eval_batch(shard=)``)
splits the padded batch POSITIONALLY over the mesh's client axes: each
shard forwards only ``bucket / S`` examples and reduces masked metric
*sums* (``repro.core.losses``), one psum adds the numerators and the true
example count, and the quotient equals the replicated masked mean — pad
rows carry zero weight on every shard and the divisor psums to the true
example count, so eval-every-round costs S× less compute per device at
the price of one tiny (3-scalar) collective.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import ClientSharding, fused_psum
from repro.core.losses import (masked_accuracy, masked_accuracy_sum,
                               masked_cross_entropy,
                               masked_cross_entropy_sum)
from repro.obs.scopes import EVAL, in_part


def make_eval_fn(bundle, fl, shard: Optional[ClientSharding] = None, *,
                 impl: str = "auto"):
    """Traceable ``eval_metrics(global_state, batch, mask) -> {acc, loss}``.

    Deployment-time logits come from the algorithm plugin's
    ``deploy_logits`` hook — for FedFusion the deployed global model
    fuses its own features with itself through the aggregated fusion
    module (E_g = E_l = global), exactly as the pre-engine evaluator did.
    ``impl`` is the kernel dispatch of that hook, as in training.

    With ``shard`` the function is a ``shard_map`` body over the client
    axes: ``batch``/``mask`` carry this shard's positional slice of the
    padded eval batch (stage with ``pad_eval_batch(shard=...)`` so the
    bucket divides), the masked sums cross shards through one psum, and
    the returned metrics are replicated-identical on every shard.
    """
    from repro.fl.api import make_algorithm   # lazy: fl sits above engine
    algo = make_algorithm(fl.algorithm)

    @in_part(EVAL)
    def eval_metrics(global_state, batch, mask) -> Dict:
        out = bundle.apply(global_state["model"], batch)
        logits = algo.deploy_logits(bundle, fl, global_state, out,
                                    impl=impl)
        labels = bundle.labels(batch)
        if shard is None:
            return {"acc": masked_accuracy(logits, labels, mask),
                    "loss": masked_cross_entropy(logits, labels, mask)}
        correct, w = masked_accuracy_sum(logits, labels, mask)
        ce, _ = masked_cross_entropy_sum(logits, labels, mask)
        sums = fused_psum({"correct": correct, "ce": ce, "w": w}, shard)
        denom = jnp.maximum(sums["w"], 1.0)
        return {"acc": sums["correct"] / denom, "loss": sums["ce"] / denom}

    return eval_metrics


def pad_eval_batch(batch, max_examples: int = 2048, sharding=None,
                   shard: Optional[int] = None) -> Tuple[Dict, jnp.ndarray]:
    """Truncate to ``max_examples``, zero-pad to a power-of-two bucket.

    Returns (padded device batch, [bucket] bool mask).  Bucketing keeps the
    compiled-shape count logarithmic in the test-set sizes seen by one
    process while never evaluating more than ~2x the requested examples.

    ``sharding`` (a ``NamedSharding``) places the padded batch and mask
    explicitly — the sharded engine passes its layout so the eval
    arguments land once at staging time instead of being re-laid-out by
    GSPMD on the first eval dispatch.

    ``shard`` (an int shard count or a ``ClientSharding``) rounds the
    bucket up so it divides evenly over the mesh's client shards — the
    positional split sharded evaluation needs; the extra rows are masked
    pad like any other.

    An empty test batch is rejected: zero valid examples make every
    masked metric an arbitrary 0/… sentinel, and silently streaming that
    into the paper's accuracy-per-round curves would be a bug, not a
    number.
    """
    key = "x" if "x" in batch else "tokens"
    n = min(len(batch[key]), max_examples)
    if n == 0:
        raise ValueError(
            "pad_eval_batch: the evaluation batch has 0 examples — masked "
            "metrics would be undefined; supply a non-empty test set or "
            "disable evaluation (eval_every=0)")
    bucket = 1
    while bucket < n:
        bucket *= 2
    bucket = min(bucket, max_examples)
    if shard is not None:
        n_shards = getattr(shard, "n_shards", shard)
        bucket = -(-bucket // n_shards) * n_shards

    def put(v):
        return jnp.asarray(v) if sharding is None else \
            jax.device_put(v, sharding)

    padded = {}
    for k, v in batch.items():
        v = np.asarray(v[:n])
        if bucket > n:
            v = np.pad(v, ((0, bucket - n),) + ((0, 0),) * (v.ndim - 1))
        padded[k] = put(v)
    mask = put(np.arange(bucket) < n)
    return padded, mask
