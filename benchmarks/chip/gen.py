"""The benchmark's own data: seeded synthetic images dealt non-IID.

Copies of ``repro.data.synth.class_images`` and
``repro.data.partition.artificial_noniid_partition`` as they stood when the
benchmark was defined (``test_bench_gen.py`` checks that they still give
the program's arrays bit for bit), so that no change to the program can
change what the benchmark feeds it.  The program receives only the arrays,
wrapped in its own ``FederatedDataset``.
"""
from __future__ import annotations

import numpy as np


def class_images(n_per_class, *, n_classes=10, shape=(28, 28, 1), seed=0,
                 noise=0.35, blobs_per_class=3, template_seed=None):
    """Returns x [N,H,W,C] float32, y [N] int32: K Gaussian-blob class
    templates plus pixel noise, shuffled."""
    t_rng = np.random.default_rng(
        seed if template_seed is None else template_seed)
    rng = np.random.default_rng(seed)
    H, W, C = shape
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    templates = np.zeros((n_classes, H, W, C), np.float32)
    my, mx = min(4, H // 4), min(4, W // 4)
    for c in range(n_classes):
        for _ in range(blobs_per_class):
            cy, cx = t_rng.uniform(my, H - my), t_rng.uniform(mx, W - mx)
            sig = t_rng.uniform(1.5, 3.5)
            amp = t_rng.uniform(0.6, 1.0)
            blob = amp * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2)
                                / (2 * sig ** 2))
            ch = t_rng.integers(0, C)
            templates[c, :, :, ch] += blob
    templates = np.clip(templates, 0, 1.5)

    xs, ys = [], []
    for c in range(n_classes):
        imgs = templates[c][None] + noise * rng.standard_normal(
            (n_per_class, H, W, C)).astype(np.float32)
        xs.append(imgs)
        ys.append(np.full(n_per_class, c, np.int32))
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    perm = rng.permutation(len(x))
    return x[perm].astype(np.float32), y[perm]


def artificial_noniid_partition(x, y, n_clients, *, shards_per_client=2,
                                seed=0):
    """Sort by label, cut into ``n_clients * shards_per_client`` shards and
    deal ``shards_per_client`` to each client (McMahan et al.'s
    pathological non-IID split, arXiv:1602.05629)."""
    rng = np.random.default_rng(seed)
    order = np.argsort(y, kind="stable")
    n_shards = n_clients * shards_per_client
    shards = np.array_split(order, n_shards)
    shard_ids = rng.permutation(n_shards)
    out = []
    for c in range(n_clients):
        ids = shard_ids[c * shards_per_client:(c + 1) * shards_per_client]
        idx = np.concatenate([shards[i] for i in ids])
        out.append({"x": x[idx], "y": y[idx]})
    return out


def federation(model, fed, seed):
    """(client shards, test set) of one run from its ``--seed``.

    ``model``: the configuration's ``model`` block (``input_shape``,
    ``n_classes``); ``fed``: the traffic's ``federation`` block.  The
    class templates are fixed by ``template_seed``, so the train split
    (``seed``) and the test split (``seed + 1``) share one distribution.
    """
    shape = tuple(model["input_shape"])
    k = model["n_classes"]
    n_train = fed["n_clients"] * fed["per_client"]
    if n_train % k or fed["n_test"] % k:
        raise ValueError("the federation and the test set must hold a "
                         "whole number of examples per class")
    x, y = class_images(n_train // k, n_classes=k, shape=shape, seed=seed,
                        noise=fed["noise"],
                        template_seed=fed["template_seed"])
    xt, yt = class_images(fed["n_test"] // k, n_classes=k, shape=shape,
                          seed=seed + 1, noise=fed["noise"],
                          template_seed=fed["template_seed"])
    parts = artificial_noniid_partition(
        x, y, fed["n_clients"], shards_per_client=fed["shards_per_client"],
        seed=seed)
    return parts, {"x": xt, "y": yt}
