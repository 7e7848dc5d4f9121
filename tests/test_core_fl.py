"""The paper's core: two-stream losses, fusion modules, round semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CNN_CONFIGS
from repro.configs.base import FLConfig
from repro.core.aggregate import (client_weighted_sum, normalize_weights,
                                  weighted_mean)
from repro.core.fusion import fusion_aggregate, fusion_apply, fusion_init
from repro.core.local import make_local_loss, make_local_trainer
from repro.core.rounds import init_global_state, make_round_fn
from repro.models.registry import make_bundle


def _cnn_bundle():
    import dataclasses
    cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"], input_shape=(12, 12, 1),
                              conv_channels=(4, 8), fc_units=(16,), dropout=0.0)
    return make_bundle(cfg)


def _cnn_batch(key, n=8):
    kx, ky = jax.random.split(key)
    return {"x": jax.random.normal(kx, (n, 12, 12, 1)),
            "y": jax.random.randint(ky, (n,), 0, 10)}


# ---------------------------------------------------------------------------
# Fusion modules (paper §3.2)
# ---------------------------------------------------------------------------

def test_fusion_conv_init_is_stream_average():
    """W0 ~= 0.5*[I;I]: at init the conv operator averages the streams."""
    C = 16
    p = fusion_init("conv", C, jax.random.PRNGKey(0))
    fg = jax.random.normal(jax.random.PRNGKey(1), (4, C))
    fl = jax.random.normal(jax.random.PRNGKey(2), (4, C))
    got = fusion_apply("conv", p, fg, fl, impl="jnp")
    np.testing.assert_allclose(got, 0.5 * (fg + fl), atol=0.05)


@pytest.mark.parametrize("op", ["multi", "single"])
def test_fusion_gates_interpolate(op):
    C = 8
    p = fusion_init(op, C, jax.random.PRNGKey(0))
    fg = jnp.ones((2, C))
    fl = -jnp.ones((2, C))
    # lam = 0.5 at init -> exact midpoint
    np.testing.assert_allclose(fusion_apply(op, p, fg, fl), 0.0, atol=1e-6)
    # lam = 1 -> global stream only
    p1 = jax.tree.map(jnp.ones_like, p)
    np.testing.assert_allclose(fusion_apply(op, p1, fg, fl), fg)


def test_fusion_multi_selects_per_channel():
    """multi's vector gate picks global for some channels, local for others
    — the paper's argument for artificial non-IID wins."""
    C = 4
    lam = jnp.array([1.0, 0.0, 1.0, 0.0])
    fg = jnp.arange(C, dtype=jnp.float32)[None]
    fl = 10 + jnp.arange(C, dtype=jnp.float32)[None]
    out = fusion_apply("multi", {"lam": lam}, fg, fl)
    np.testing.assert_allclose(out[0], [0.0, 11.0, 2.0, 13.0])


def test_fusion_aggregate_conv_is_weighted_mean():
    C = 4
    f1 = fusion_init("conv", C, jax.random.PRNGKey(1))
    f2 = fusion_init("conv", C, jax.random.PRNGKey(2))
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), f1, f2)
    w = jnp.array([0.25, 0.75])
    out = fusion_aggregate("conv", f1, stacked, w, ema_beta=0.5)
    np.testing.assert_allclose(out["w"], 0.25 * f1["w"] + 0.75 * f2["w"],
                               rtol=1e-6)


@pytest.mark.parametrize("op", ["multi", "single"])
def test_fusion_aggregate_gates_use_ema(op):
    """Paper §3.3: multi/single gates are EMA-smoothed at aggregation."""
    C = 4
    old = fusion_init(op, C, jax.random.PRNGKey(0))       # lam = 0.5
    client = jax.tree.map(jnp.ones_like, old)             # client gate = 1
    stacked = jax.tree.map(lambda x: x[None], client)
    out = fusion_aggregate(op, old, stacked, jnp.array([1.0]), ema_beta=0.8)
    np.testing.assert_allclose(out["lam"], 0.8 * 0.5 + 0.2 * 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# Two-stream losses (paper §3.1)
# ---------------------------------------------------------------------------

def test_fedmmd_loss_adds_positive_regularizer_after_drift():
    bundle = _cnn_bundle()
    fl_avg = FLConfig(algorithm="fedavg")
    fl_mmd = FLConfig(algorithm="fedmmd", mmd_lambda=1.0)
    params = bundle.init(jax.random.PRNGKey(0))
    drifted = jax.tree.map(lambda x: x + 0.3, params)
    batch = _cnn_batch(jax.random.PRNGKey(1))
    l_avg, _ = make_local_loss(bundle, fl_avg)({"model": drifted}, params, batch)
    l_mmd, aux = make_local_loss(bundle, fl_mmd)({"model": drifted}, params, batch)
    assert float(l_mmd) > float(l_avg)
    assert float(aux["mmd"]) > 0


def test_fedmmd_equals_fedavg_when_streams_identical():
    """MMD(theta_G(X), theta_L(X)) == 0 when theta_L == theta_G."""
    bundle = _cnn_bundle()
    params = bundle.init(jax.random.PRNGKey(0))
    batch = _cnn_batch(jax.random.PRNGKey(1))
    l_avg, _ = make_local_loss(bundle, FLConfig(algorithm="fedavg"))(
        {"model": params}, params, batch)
    l_mmd, aux = make_local_loss(bundle, FLConfig(algorithm="fedmmd"))(
        {"model": params}, params, batch)
    np.testing.assert_allclose(float(l_mmd), float(l_avg), atol=1e-5)
    assert abs(float(aux["mmd"])) < 1e-6


def test_fedl2_penalizes_parameter_distance():
    bundle = _cnn_bundle()
    fl = FLConfig(algorithm="fedl2", l2_lambda=1.0)
    params = bundle.init(jax.random.PRNGKey(0))
    drifted = jax.tree.map(lambda x: x + 0.1, params)
    batch = _cnn_batch(jax.random.PRNGKey(1))
    loss_fn = make_local_loss(bundle, fl)
    _, aux0 = loss_fn({"model": params}, params, batch)
    _, aux1 = loss_fn({"model": drifted}, params, batch)
    assert float(aux0["l2"]) < 1e-6
    assert float(aux1["l2"]) > 0.01


def test_frozen_global_gets_no_gradient():
    """Paper Fig. 1: the global stream is FIXED; only trainable moves."""
    bundle = _cnn_bundle()
    fl = FLConfig(algorithm="fedmmd", mmd_lambda=1.0)
    params = bundle.init(jax.random.PRNGKey(0))
    drifted = jax.tree.map(lambda x: x + 0.2, params)
    batch = _cnn_batch(jax.random.PRNGKey(1))
    loss_fn = make_local_loss(bundle, fl)
    g_global = jax.grad(lambda gp: loss_fn({"model": drifted}, gp, batch)[0])(
        params)
    assert max(float(jnp.abs(g).max()) for g in jax.tree.leaves(g_global)) == 0


def test_fedfusion_local_step_trains_fusion_module():
    bundle = _cnn_bundle()
    fl = FLConfig(algorithm="fedfusion", fusion_op="conv", local_steps=3,
                  lr=0.1)
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    trainer = make_local_trainer(bundle, fl)
    batches = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_cnn_batch(jax.random.PRNGKey(i)) for i in range(3)])
    trainable, loss = trainer(state["model"], state["fusion"], batches,
                              jnp.float32(0.1))
    dw = float(jnp.abs(trainable["fusion"]["w"] - state["fusion"]["w"]).max())
    assert dw > 1e-6
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# Round semantics (paper Alg. 1 / Alg. 2)
# ---------------------------------------------------------------------------

def _round_batches(key, n_clients=4, steps=2, n=4):
    ks = jax.random.split(key, n_clients * steps)
    per = [_cnn_batch(k, n) for k in ks]
    return jax.tree.map(
        lambda *xs: jnp.stack(xs).reshape((n_clients, steps) + xs[0].shape),
        *per)


def test_parallel_and_sequential_rounds_agree():
    """The two mesh-execution modes are the SAME algorithm."""
    bundle = _cnn_bundle()
    fl = FLConfig(algorithm="fedavg", local_steps=2, lr=0.05)
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    batches = _round_batches(jax.random.PRNGKey(1))
    nex = jnp.array([1.0, 2.0, 3.0, 4.0])
    out_p, _ = make_round_fn(bundle, fl, "client_parallel")(
        state, batches, nex, jnp.float32(0.05))
    out_s, _ = make_round_fn(bundle, fl, "client_sequential")(
        state, batches, nex, jnp.float32(0.05))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5),
        out_p["model"], out_s["model"])


def test_single_client_round_equals_local_training():
    """With one client of weight 1, the round IS that client's local run."""
    bundle = _cnn_bundle()
    fl = FLConfig(algorithm="fedavg", local_steps=2, lr=0.05)
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    batches = _round_batches(jax.random.PRNGKey(1), n_clients=1)
    new_state, _ = make_round_fn(bundle, fl, "client_parallel")(
        state, batches, jnp.ones(1), jnp.float32(0.05))
    trainer = make_local_trainer(bundle, fl)
    want, _ = trainer(state["model"], None,
                      jax.tree.map(lambda x: x[0], batches), jnp.float32(0.05))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 new_state["model"], want["model"])


def test_weighted_mean_respects_n_t():
    """Server aggregation is the n_t-weighted average (Alg. 2 line 7)."""
    t1 = {"w": jnp.ones((2, 2))}
    t2 = {"w": 3 * jnp.ones((2, 2))}
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), t1, t2)
    w = normalize_weights(jnp.array([300.0, 100.0]))
    out = weighted_mean(stacked, w)
    np.testing.assert_allclose(out["w"], 1.5)  # 0.75*1 + 0.25*3



def test_client_weighted_sum_adds_clients_in_order():
    """Clients are added one after another from zero.  In f32, 1e8 + 1
    rounds back to 1e8, so the association shows in the result: client
    order gives (1e8 - 1e8) + 1 = 1 and (1 + 1e8) - 1e8 = 0, where a
    reordered reduction would give another answer in one column."""
    x = {"w": jnp.array([[1e8, 1.0], [-1e8, 1e8], [1.0, -1e8]], jnp.float32)}
    got = jax.jit(client_weighted_sum)(jnp.ones((3,), jnp.float32), x)
    np.testing.assert_array_equal(got["w"], np.array([1.0, 0.0], np.float32))
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    stacked = {"w": jax.random.normal(k[0], (10, 5, 5, 8)),
               "b": jax.random.normal(k[1], (10, 7))}
    w = normalize_weights(jnp.arange(1.0, 11.0))
    got = jax.jit(client_weighted_sum)(w, stacked)
    for key in stacked:
        np.testing.assert_allclose(
            got[key], jnp.tensordot(w, stacked[key], axes=1), rtol=1e-5,
            atol=1e-6)


def test_identical_clients_fixed_point():
    """If every client computes the same update, averaging preserves it."""
    bundle = _cnn_bundle()
    fl = FLConfig(algorithm="fedavg", local_steps=1, lr=0.05)
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    one = _cnn_batch(jax.random.PRNGKey(1))
    batches = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None, None], (3, 1) + x.shape), one)
    new_state, _ = make_round_fn(bundle, fl, "client_parallel")(
        state, batches, jnp.ones(3), jnp.float32(0.05))
    trainer = make_local_trainer(bundle, fl)
    want, _ = trainer(state["model"], None,
                      jax.tree.map(lambda x: x[None], one), jnp.float32(0.05))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 new_state["model"], want["model"])


@pytest.mark.parametrize("algo", ["fedfusion", "fedmmd"])
def test_cached_global_features_identical(algo):
    """Paper §3.3: E_g's features can be recorded once per round.  With
    E local epochs the cached path must be bit-identical to recompute."""
    bundle = _cnn_bundle()
    batches = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_cnn_batch(jax.random.PRNGKey(i)) for i in range(3)])
    outs = {}
    for cache in (True, False):
        fl = FLConfig(algorithm=algo, fusion_op="conv", local_steps=3,
                      local_epochs=2, cache_global_features=cache, lr=0.05)
        state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
        trainer = make_local_trainer(bundle, fl)
        outs[cache] = trainer(state["model"], state.get("fusion"), batches,
                              jnp.float32(0.05))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 outs[True][0], outs[False][0])


def test_multi_epoch_training_progresses():
    bundle = _cnn_bundle()
    fl1 = FLConfig(algorithm="fedavg", local_steps=2, local_epochs=1, lr=0.05)
    fl3 = FLConfig(algorithm="fedavg", local_steps=2, local_epochs=3, lr=0.05)
    state = init_global_state(bundle, fl1, jax.random.PRNGKey(0))
    batches = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_cnn_batch(jax.random.PRNGKey(i)) for i in range(2)])
    t1, _ = make_local_trainer(bundle, fl1)(state["model"], None, batches,
                                            jnp.float32(0.05))
    t3, _ = make_local_trainer(bundle, fl3)(state["model"], None, batches,
                                            jnp.float32(0.05))
    # 3 epochs move farther from the init than 1
    d1 = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(t1["model"]), jax.tree.leaves(state["model"])))
    d3 = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(t3["model"]), jax.tree.leaves(state["model"])))
    assert d3 > d1
