"""The benchmark's copy of the data generator and partition gives the
program's own arrays bit for bit."""
from __future__ import annotations

import numpy as np
import pytest

import gen


@pytest.mark.parametrize("shape", [(28, 28, 1), (32, 32, 3)])
def test_copied_generator_and_partition_match_the_program(shape):
    from repro.data.partition import artificial_noniid_partition
    from repro.data.synth import class_images
    seed = 2 ** 31 + 11
    kw = dict(n_classes=10, shape=shape, seed=seed, noise=0.2,
              template_seed=0)
    x, y = gen.class_images(60, **kw)
    px, py = class_images(60, **kw)
    assert x.dtype == px.dtype and np.array_equal(x, px)
    assert np.array_equal(y, py)
    mine = gen.artificial_noniid_partition(x, y, 20, shards_per_client=2,
                                           seed=seed)
    theirs = artificial_noniid_partition(px, py, 20, shards_per_client=2,
                                         seed=seed)
    assert len(mine) == len(theirs) == 20
    for a, b in zip(mine, theirs):
        assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"],
                                                                 b["y"])


def test_federation_deals_every_client_the_same_count():
    model = {"input_shape": [28, 28, 1], "n_classes": 10}
    fed = {"n_clients": 10, "per_client": 60, "n_test": 100,
           "shards_per_client": 2, "noise": 0.2, "template_seed": 0}
    parts, test = gen.federation(model, fed, 7)
    assert [len(p["y"]) for p in parts] == [60] * 10
    assert test["x"].shape == (100, 28, 28, 1)
    # two label shards each: at most two classes (one where they meet)
    assert all(len(np.unique(p["y"])) <= 2 for p in parts)
