"""Each one-chip cell's superstep compiles for a described TPU v5e.

The superstep the window runs, built as the engine builds it (Pallas
kernels, eval folded into the round scan, the engine's donation), at the
cell's real shapes, compiled by the TPU compiler for a chip that is
described and not attached.  Prints the compiler's memory analysis, which
PERF.md sets beside each cell's measured ``peak_hbm_mb``.
"""
from __future__ import annotations

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import spec  # noqa: E402

ONE_CHIP = [w["name"] for w in spec.benchmark()["workloads"]
            if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def superstep_and_args(cell, sharding):
    import jax
    import jax.numpy as jnp

    from repro.compress import make_codec
    from repro.configs.base import CNNConfig, FLConfig
    from repro.engine.evaljit import make_eval_fn
    from repro.engine.superstep import (abstract_superstep_args,
                                        donation_argnums,
                                        make_compressed_superstep,
                                        make_plain_superstep)
    from repro.models.registry import make_bundle
    m, t = cell.model, cell.traffic
    cfg = CNNConfig(name=cell.config["name"],
                    input_shape=tuple(m["input_shape"]),
                    conv_channels=tuple(m["conv_channels"]),
                    pool_size=m["pool_size"], pool_stride=m["pool_stride"],
                    fc_units=tuple(m["fc_units"]), n_classes=m["n_classes"],
                    dropout=m["dropout"])
    fl = FLConfig(**{k: tuple(v) if isinstance(v, list) else v
                     for k, v in t["fl"].items()})
    bundle = make_bundle(cfg)
    k = t["superstep_rounds"]
    eval_fn = make_eval_fn(bundle, fl, impl="pallas")
    n_eval = min(t["federation"]["n_test"], t["eval_examples"])
    test = ({"x": jax.ShapeDtypeStruct((n_eval,) + cfg.input_shape,
                                       jnp.float32),
             "y": jax.ShapeDtypeStruct((n_eval,), jnp.int32)},
            jax.ShapeDtypeStruct((n_eval,), jnp.bool_))
    if fl.compressed:
        up = make_codec(fl.uplink_codec, topk_frac=fl.topk_frac,
                        impl="pallas")
        down = make_codec(fl.downlink_codec, impl="pallas")
        model = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        up.bind(model)
        down.bind(model)
        fn = make_compressed_superstep(bundle, fl, "client_parallel", k, up,
                                       down, eval_fn=eval_fn, impl="pallas")
        args = abstract_superstep_args(
            bundle, fl, k, cohort=fl.clients_per_round, uplink=up,
            ef_rows=t["federation"]["n_clients"])
    else:
        fn = make_plain_superstep(bundle, fl, "client_parallel", k,
                                  eval_fn=eval_fn, impl="pallas")
        args = abstract_superstep_args(bundle, fl, k,
                                       cohort=fl.clients_per_round)
    args = tuple(args) + test
    placed = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn, donate_argnums=donation_argnums(
        compressed=fl.compressed)), placed


@pytest.mark.parametrize("name", ONE_CHIP)
def test_superstep_compiles_for_v5e(one_chip, name):
    cell = spec.Cell.load(name)
    step, args = superstep_and_args(cell, one_chip)
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    sizes = {k: getattr(mem, f"{k}_size_in_bytes")
             for k in ("argument", "output", "temp", "alias")}
    print(f"{name}: {sizes}")
    assert sizes["argument"] > 0
    assert "tpu_custom_call" in compiled.as_text() or \
        cell.traffic["fl"]["algorithm"] == "fedavg"
