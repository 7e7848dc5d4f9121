"""The main path's Pallas kernels compile for a TPU v5e chip.

Each kernel is compiled ahead of time, ``interpret=False``, at the paper's
MNIST CNN widths for one chip of a described ``v5e:2x2`` topology: the
TPU's compiler runs here, with no chip attached, and refuses what the chip
would refuse (unaligned blocks, memory spaces a kernel may not load from,
lane shuffles it cannot lower).  Nothing runs, so the values are the
business of ``test_kernels.py`` / ``test_compress.py``; this file pins
that every kernel is a ``tpu_custom_call`` of the compiled program.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU's library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compress_pack, fusion_conv, mk_mmd

# the paper's MNIST CNN (configs/cnn_paper.py): its largest leaf is the
# 3136 x 512 FC weight, the conv features are [B, 7, 7, 64] at batch 50,
# and the federation has 100 clients (the EF table's rows)
N_FC = 7 * 7 * 64 * 512
N_CONV2 = 5 * 5 * 32 * 64
FEATS = (50, 7, 7, 64)
N_CLIENTS, COHORT = 100, 10
WIDTHS = (1.0, 2.0, 4.0, 8.0, 16.0)


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(dev):
    f32, i32 = jnp.float32, jnp.int32
    s = lambda shape, dt=f32: _spec(shape, dt, dev)  # noqa: E731
    cp = compress_pack
    return {
        "quant_pack_int8": (
            lambda x, sc, u: cp.quant_pack(x, sc, u, bits=8),
            (s((N_FC,)), s(()), s((N_FC,)))),
        "quant_pack_int4": (
            lambda x, sc, u: cp.quant_pack(x, sc, u, bits=4),
            (s((N_FC,)), s(()), s((N_FC,)))),
        "quant_unpack_int8": (
            lambda q, sc: cp.quant_unpack(q, sc, bits=8),
            (s((N_FC,), jnp.int8), s(()))),
        "quant_unpack_int4": (
            lambda q, sc: cp.quant_unpack(q, sc, bits=4, n=N_FC),
            (s((N_FC // 2,), jnp.uint8), s(()))),
        "topk_select": (
            lambda x, t: cp.topk_select(x, t),
            (s((N_CONV2,)), s(()))),
        "ef_gather": (
            lambda t, i: cp.ef_gather(t, i),
            (s((N_CLIENTS, N_FC)), s((COHORT,), i32))),
        "ef_scatter": (
            lambda t, i, r: cp.ef_scatter(t, i, r),
            (s((N_CLIENTS, N_FC)), s((COHORT,), i32), s((COHORT, N_FC)))),
        "gram_sum": (
            lambda x, y, sg: mk_mmd.gram_sum(x, y, sg, WIDTHS),
            (s((FEATS[0], FEATS[-1])), s((FEATS[0], FEATS[-1])), s(()))),
        "fusion_conv": (
            lambda a, b, w: fusion_conv.fusion_conv(a, b, w),
            (s(FEATS), s(FEATS), s((2 * FEATS[-1], FEATS[-1])))),
    }


KERNELS = ("quant_pack_int8", "quant_pack_int4", "quant_unpack_int8",
           "quant_unpack_int4", "topk_select", "ef_gather", "ef_scatter",
           "gram_sum", "fusion_conv")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls, f"{name}: no tpu_custom_call in the compiled program"
    assert any(name in ln.split("=", 1)[0] for ln in calls), \
        f"{name}: the tpu_custom_call is not named after the kernel"


@pytest.mark.parametrize("name", ["gram_sum", "fusion_conv"])
def test_kernel_gradient_compiles_for_v5e(one_chip, name):
    """The two kernels on the local-training path are differentiated:
    their custom VJPs compile for the chip next to the forward kernel
    (the squared loss keeps the forward's value live)."""
    fn, args = _cases(one_chip)[name]
    n_diff = 2 if name == "gram_sum" else 3
    grad = jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                    argnums=tuple(range(n_diff)))
    text = jax.jit(grad).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("ladder", [None, (0.01, 0.05)])
def test_topk_roundtrip_compiles_without_sort_or_scatter(one_chip, ladder):
    """The round's top-k uplink (10 clients vmapped over the FC weight, a
    5% top-k with error feedback, static and on a traced ladder level)
    compiles for the chip as counting passes and selects: no ``sort``
    (what ``lax.top_k`` lowers to) and no ``scatter`` (a sparse decode or
    residual) is left in the optimized program."""
    from repro.compress import TopKCodec
    c = TopKCodec(0.05).bind([jax.ShapeDtypeStruct((N_FC,), jnp.float32)])
    if ladder is not None:
        c.set_ladder(ladder)
    level = None if ladder is None else _spec((), jnp.int32, one_chip)
    rows = _spec((COHORT, N_FC), jnp.float32, one_chip)

    def step(x, ef, lv):
        return jax.vmap(lambda a, s: c.roundtrip([a], [s], level=lv))(x, ef)

    text = jax.jit(step).lower(rows, rows, level).compile().as_text()
    ops = re.findall(r"\s([a-z][\w-]*)\(", text)
    assert ops, "no op found in the compiled program"
    assert "sort" not in ops and "scatter" not in ops
