"""Classification / LM losses + the two-stream local objectives."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy(logits, labels):
    """logits [..., V]; labels [...] int -> scalar mean CE.

    The gold logit is gathered via a one-hot contraction (not
    take_along_axis): with the vocabulary dim sharded over the `model` mesh
    axis this fuses to a masked local reduction + psum instead of a
    cross-shard gather.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(logz - gold)


def accuracy(logits, labels):
    return jnp.mean(jnp.argmax(logits, axis=-1) == labels)


def _broadcast_mask(mask, labels):
    """Per-example mask [B] -> weights broadcast to the labels' shape
    ([B] for classification, [B, S] for LM token labels)."""
    mask = mask.astype(jnp.float32)
    return jnp.broadcast_to(
        mask.reshape(mask.shape + (1,) * (labels.ndim - mask.ndim)),
        labels.shape)


def _tree_sum(v):
    """Sum of every element of ``v`` in one fixed pairwise order.

    A ``reduce`` may be emitted with a different association wherever XLA
    fuses it (a standalone evaluator vs. the same evaluator inside a
    superstep's scan body), which moves a float sum by an ulp.  Halving
    with elementwise adds pins the order, so both evaluators agree bit for
    bit; the zero padding to a power of two adds nothing."""
    v = v.reshape(-1)
    n = 1 << max(v.shape[0] - 1, 0).bit_length()
    v = jnp.pad(v, (0, n - v.shape[0]))
    while v.shape[0] > 1:
        h = v.shape[0] // 2
        v = v[:h] + v[h:]
    return v[0]


def masked_cross_entropy_sum(logits, labels, mask):
    """Masked CE *sum* and weight sum: ``(Σ ce·w, Σ w)``.

    The un-normalized form is what cross-shard evaluation psums — each
    shard reduces its slice of the padded batch, one collective adds the
    numerators and the true example count, and the quotient equals the
    full-batch masked mean (pad rows carry zero weight on every shard)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    w = _broadcast_mask(mask, labels)
    return _tree_sum((logz - gold) * w), jnp.sum(w)


def masked_accuracy_sum(logits, labels, mask):
    """Masked correct-prediction *sum* and weight sum: ``(Σ 1[correct]·w,
    Σ w)`` — the psum-able form of :func:`masked_accuracy`."""
    correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    w = _broadcast_mask(mask, labels)
    return jnp.sum(correct * w), jnp.sum(w)


def masked_cross_entropy(logits, labels, mask):
    """Mean CE over the valid examples only (mask [B] bool/float).

    The padded tail of a fixed-shape eval batch contributes zero weight, so
    one compiled evaluator serves any test-set size (repro.fl.server)."""
    ce_sum, w_sum = masked_cross_entropy_sum(logits, labels, mask)
    return ce_sum / jnp.maximum(w_sum, 1.0)


def masked_accuracy(logits, labels, mask):
    """Accuracy over the valid examples only (mask [B] bool/float)."""
    correct_sum, w_sum = masked_accuracy_sum(logits, labels, mask)
    return correct_sum / jnp.maximum(w_sum, 1.0)


def l2_tree_distance(tree_a, tree_b):
    """Sum of squared parameter distances (the paper's L2 two-stream
    baseline constraint)."""
    leaves = jax.tree.map(
        lambda a, b: jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))),
        tree_a, tree_b)
    return jax.tree.reduce(jnp.add, leaves)
