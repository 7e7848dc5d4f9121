"""repro.compress: codec round trips, wire accounting, kernel parity,
error-feedback convergence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import CODEC_NAMES, IdentityCodec, QuantCodec, \
    SketchCodec, TopKCodec, make_codec
from repro.configs import CNN_CONFIGS
from repro.configs.base import CODEC_NAMES as CONFIG_CODEC_NAMES, FLConfig
from repro.core.rounds import (init_global_state, make_compressed_round_fn,
                               make_round_fn)
from repro.fl.comm import CommLog, tree_bytes
from repro.kernels import ops, ref
from repro.models.registry import make_bundle


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    return {"w": jax.random.normal(k1, (37, 24)),
            "b": jax.random.normal(k2, (11,)),
            "deep": {"v": jax.random.normal(k3, (130,))}}


# ---------------------------------------------------------------------------
# Round trips
# ---------------------------------------------------------------------------

def test_identity_roundtrip_exact_and_raw_bytes():
    t = _tree()
    c = IdentityCodec().bind(t)
    p, _ = c.encode(t)
    for a, b in zip(jax.tree.leaves(c.decode(p)), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert c.nbytes(p) == tree_bytes(t)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("stochastic", [False, True])
def test_quant_roundtrip_within_one_step(bits, stochastic):
    t = _tree()
    c = QuantCodec(bits, impl="jnp").bind(t)
    key = jax.random.PRNGKey(3) if stochastic else None
    p, _ = c.encode(t, None, key)
    dec = c.decode(p)
    qmax = 127 if bits == 8 else 7
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(t)):
        scale = float(jnp.max(jnp.abs(b))) / qmax
        assert float(jnp.max(jnp.abs(a - b))) <= scale * (1 + 1e-5)


def test_quant_stochastic_rounding_is_unbiased():
    x = {"w": jnp.full((4096,), 0.3)}
    c = QuantCodec(4, impl="jnp").bind(x)
    p, _ = c.encode(x, None, jax.random.PRNGKey(0))
    dec = c.decode(p)["w"]
    # codes straddle 0.3/scale; the mean must land near 0.3, not on a grid
    # point (deterministic rounding would give max|err| for every element)
    assert abs(float(jnp.mean(dec)) - 0.3) < 0.005


def test_topk_full_frac_roundtrip_exact():
    t = _tree()
    c = TopKCodec(1.0, impl="jnp").bind(t)
    p, _ = c.encode(t, c.init_state())
    for a, b in zip(jax.tree.leaves(c.decode(p)), jax.tree.leaves(t)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_topk_keeps_largest_and_ef_accumulates_remainder():
    t = {"w": jnp.asarray([0.1, -3.0, 0.2, 2.0, -0.05])}
    c = TopKCodec(0.4, impl="jnp").bind(t)   # k = 2 of 5
    st = c.init_state()
    p, new_st = c.encode(t, st)
    dec = c.decode(p)["w"]
    np.testing.assert_allclose(np.asarray(dec), [0, -3.0, 0, 2.0, 0],
                               atol=1e-7)
    # residual holds exactly what was dropped: decoded + residual == input
    np.testing.assert_allclose(np.asarray(dec) + np.asarray(new_st[0]),
                               np.asarray(t["w"]), atol=1e-7)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


_SPECIALS = np.array([np.inf, -np.inf, np.nan, -np.nan, 0.0, -0.0, 1.0, -1.0,
                      np.float32(2.0 ** -140)], np.float32)   # a subnormal


def _data(kind, shape, rng):
    """Leaf data whose k-th magnitude is continuous, heavily tied, all
    zeros of both signs, or drawn from infinities, NaNs and zeros."""
    x = rng.standard_normal(shape).astype(np.float32)
    if kind == "ties":
        return np.round(2 * x).astype(np.float32)
    if kind == "zeros":
        return np.where(rng.random(shape) < 0.5, 0.0, -0.0).astype(np.float32)
    if kind == "specials":
        return rng.choice(_SPECIALS, shape)
    return x


# (data, frac, error feedback, mode).  Of the leaves (888, 11, 130, 1),
# frac 0.001 keeps k = 1 of each, 0.95 keeps k = n of the 11, and the
# 1-element leaf always keeps k = n.  "ladder" runs every
# rung of (frac/20, frac/4, frac) with a traced level; "vmap" runs 10
# clients at a leaf of 2**20 + 7 entries.
TOPK_CASES = ([(d, f, ef, "static")
               for d in ("gauss", "ties", "zeros", "specials")
               for f in (0.001, 0.05, 0.95, 1.0) for ef in (True, False)]
              + [(d, 0.4, ef, "ladder") for d in ("gauss", "ties", "specials")
                 for ef in (True, False)]
              + [("gauss", 0.05, True, "vmap"), ("ties", 0.05, False, "vmap")])


@pytest.mark.parametrize("data,frac,ef,mode", TOPK_CASES)
def test_topk_roundtrip_matches_decode_of_encode_bitwise(data, frac, ef, mode):
    """``TopKCodec.roundtrip`` (sort-free selection, dense build) equals
    ``decode(encode(...))`` and ``encode``'s residual bit for bit: the same
    k entries, ties to the lower index as ``lax.top_k``, +0.0 where the
    scatter writes it."""
    rng = np.random.default_rng(TOPK_CASES.index((data, frac, ef, mode)))
    shapes = ([(2 ** 20 + 7,)] if mode == "vmap"
              else [(37, 24), (11,), (130,), (1,)])
    lead = (10,) if mode == "vmap" else ()
    tree = [jnp.asarray(_data(data, lead + s, rng)) for s in shapes]
    c = TopKCodec(frac, error_feedback=ef).bind(
        [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes])
    if mode == "ladder":
        c.set_ladder((frac / 20, frac / 4, frac))
    # a non-zero residual, so g = x + state is what is selected from
    state = ([jnp.asarray(_data(data, lead + (int(np.prod(s)),), rng))
              for s in shapes] if ef else c.init_state())

    def want(t, s, level):
        payload, new_state = c.encode(t, s, level=level)
        return c.decode(payload), new_state

    def got(t, s, level):
        return c.roundtrip(t, s, level=level)

    if mode == "vmap":
        runs = [(jax.jit(jax.vmap(lambda t, s: want(t, s, None)))(tree, state),
                 jax.jit(jax.vmap(lambda t, s: got(t, s, None)))(tree, state))]
    else:
        levels = ([jnp.int32(lv) for lv in range(3)] if mode == "ladder"
                  else [None])
        runs = [(want(tree, state, lv), jax.jit(got)(tree, state, lv))
                for lv in levels]
    for (dec_w, st_w), (dec_g, st_g) in runs:
        for a, b in zip(jax.tree.leaves((dec_w, st_w)),
                        jax.tree.leaves((dec_g, st_g))):
            assert a.shape == b.shape
            np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("name", ["identity", "int8", "int4", "mask"])
def test_default_roundtrip_traces_decode_of_encode(name):
    """Every codec but top-k keeps the default ``roundtrip``, so the round
    traces the program it traced as ``decode(encode(...))``."""
    t = _tree()
    c = make_codec(name, topk_frac=0.2).bind(t)
    key = jax.random.PRNGKey(0) if c.uses_key else None

    def via_encode(t, s, k):
        payload, new_state = c.encode(t, s, k)
        return c.decode(payload), new_state

    got = jax.make_jaxpr(c.roundtrip)(t, c.init_state(), key)
    want = jax.make_jaxpr(via_encode)(t, c.init_state(), key)
    assert str(got) == str(want)


def test_mask_full_frac_roundtrip_exact():
    t = _tree()
    c = SketchCodec(1.0, mode="mask", impl="jnp").bind(t)
    p, _ = c.encode(t, None, jax.random.PRNGKey(5))
    for a, b in zip(jax.tree.leaves(c.decode(p)), jax.tree.leaves(t)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_lowrank_sketch_is_unbiased():
    """E[U G^T] = X over independent sketch seeds."""
    x = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 32))}
    c = SketchCodec(0.25, mode="lowrank", impl="jnp").bind(x)
    acc = np.zeros((16, 32))
    n = 300
    for s in range(n):
        p, _ = c.encode(x, None, jax.random.PRNGKey(1000 + s))
        acc += np.asarray(c.decode(p)["w"])
    err = np.abs(acc / n - np.asarray(x["w"])).max()
    # single-decode error is ~9 here; the 300-seed mean must collapse
    # toward 0 (it would stay ~9 if the estimator were biased)
    assert err < 0.8, err


# ---------------------------------------------------------------------------
# Wire accounting
# ---------------------------------------------------------------------------

def test_nbytes_monotone_in_topk_frac():
    t = _tree()
    sizes = []
    for frac in (0.01, 0.1, 0.5, 1.0):
        c = TopKCodec(frac, impl="jnp").bind(t)
        sizes.append(c.wire_bytes())
    assert sizes == sorted(sizes) and sizes[0] < sizes[-1]


def test_nbytes_monotone_in_quant_bits():
    t = _tree()
    b4 = QuantCodec(4, impl="jnp").bind(t).wire_bytes()
    b8 = QuantCodec(8, impl="jnp").bind(t).wire_bytes()
    assert b4 < b8 < tree_bytes(t)


def test_wire_bytes_matches_concrete_payload():
    t = _tree()
    for name in CODEC_NAMES:
        c = make_codec(name, topk_frac=0.2).bind(t)
        p, _ = c.encode(t, c.init_state(),
                        jax.random.PRNGKey(0) if c.uses_key else None)
        assert c.wire_bytes() == c.nbytes(p), name


def test_config_codec_names_in_sync():
    assert set(CONFIG_CODEC_NAMES) == set(CODEC_NAMES)
    with pytest.raises(ValueError, match="uplink_codec"):
        FLConfig(uplink_codec="gzip")


def test_make_codec_rejects_out_of_range_params():
    """Construction-time rejection, not just FLConfig validation: codecs
    built directly (tests, benchmarks, plugins) get the same errors."""
    for frac in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError, match="topk_frac"):
            make_codec("topk", topk_frac=frac)
        with pytest.raises(ValueError, match="topk_frac"):
            make_codec("mask", topk_frac=frac)
    for bits in (2, 16, 0):
        with pytest.raises(ValueError, match="quant_bits"):
            make_codec("quant", quant_bits=bits)
    with pytest.raises(ValueError, match="unknown codec"):
        make_codec("gzip")
    # direct constructors carry the same guards
    with pytest.raises(ValueError, match="frac"):
        TopKCodec(0.0)
    with pytest.raises(ValueError, match="bits"):
        QuantCodec(3)


def test_commlog_wire_bytes_below_idealized():
    state = {"model": {"w": jnp.zeros((1000,), jnp.float32)}}
    c = make_codec("int8").bind(state["model"])
    wire = c.wire_bytes()
    assert wire < tree_bytes(state["model"])
    log = CommLog()
    log.log_round(state, 4, {}, wire_up=wire, wire_down=wire)
    assert log.bytes_up == 4 * wire
    assert log.bytes_up < log.history[0]["bytes_up_ideal"]
    # uncompressed default unchanged
    raw = CommLog()
    raw.log_round(state, 4, {})
    assert raw.bytes_up == 4 * tree_bytes(state["model"])


def test_commlog_mirror_downlink_charges_all_clients():
    """A mirror-stream downlink is a multicast: every client of the
    federation receives every round's update, not just the sampled ones."""
    state = {"model": {"w": jnp.zeros((1000,), jnp.float32)}}
    log = CommLog()
    log.log_round(state, 4, {}, wire_down=100, n_down=64)
    assert log.bytes_down == 64 * 100
    assert log.bytes_up == 4 * tree_bytes(state["model"])
    # fusion module goes to the round's participants only, not the stream
    state_f = dict(state, fusion={"w": jnp.zeros((10,), jnp.float32)})
    log2 = CommLog()
    log2.log_round(state_f, 4, {}, wire_down=100, n_down=64)
    assert log2.bytes_down == 64 * 100 + 4 * 40


# ---------------------------------------------------------------------------
# Pallas kernels vs jnp references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("n", [64, 1024, 2050 * 2])
def test_quant_pack_pallas_matches_ref_exactly(bits, n):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n + bits))
    x = jax.random.normal(k1, (n,))
    noise = jax.random.uniform(k2, (n,))
    scale = jnp.max(jnp.abs(x)) / (127 if bits == 8 else 7)
    want = ref.quant_pack_ref(x, scale, noise, bits=bits)
    got = ops.quantize_pack(x, scale, noise, bits=bits,
                            impl="pallas_interpret")
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # unpack parity on the float side
    w = ref.quant_unpack_ref(want, scale, bits=bits, n=n)
    g = ops.quantize_unpack(got, scale, bits=bits, n=n,
                            impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


@pytest.mark.parametrize("n,k", [(100, 10), (1500, 1), (4096, 400)])
def test_topk_select_pallas_matches_ref(n, k):
    x = jax.random.normal(jax.random.PRNGKey(k), (n,))
    thresh = jnp.sort(jnp.abs(x))[-k]
    want = ref.topk_select_ref(x, thresh)
    got = ops.topk_threshold_select(x, thresh, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert int(jnp.sum(got != 0)) == k


# ---------------------------------------------------------------------------
# Round integration
# ---------------------------------------------------------------------------

def _tiny_setup(algorithm="fedavg"):
    cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"],
                              input_shape=(12, 12, 1), conv_channels=(4, 8),
                              fc_units=(16,), dropout=0.0)
    bundle = make_bundle(cfg)
    fl = FLConfig(algorithm=algorithm, clients_per_round=4, local_steps=2,
                  local_batch=8, lr=0.05)
    return bundle, fl


def _round_inputs(key, n_clients=4, steps=2, batch=8):
    kx, ky = jax.random.split(key)
    batches = {"x": jax.random.normal(kx, (n_clients, steps, batch,
                                           12, 12, 1)),
               "y": jax.random.randint(ky, (n_clients, steps, batch), 0, 10)}
    sizes = jnp.asarray([40.0, 30.0, 20.0, 10.0])
    return batches, sizes


@pytest.mark.parametrize("mode", ["client_parallel", "client_sequential"])
def test_identity_codecs_reproduce_plain_round(mode):
    """encode/decode through identity == the classic FedAvg round."""
    bundle, fl = _tiny_setup()
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    batches, sizes = _round_inputs(jax.random.PRNGKey(1))
    plain = make_round_fn(bundle, fl, mode)
    up = IdentityCodec().bind(state["model"])
    down = IdentityCodec().bind(state["model"])
    comp = make_compressed_round_fn(bundle, fl, mode, up, down)
    ef = jax.tree.map(lambda z: jnp.stack([z] * 4), up.init_state())
    want, wm = plain(state, batches, sizes, 0.05)
    got, gm, _, _ = comp(state, batches, sizes, 0.05, ef, state["model"],
                         jax.random.PRNGKey(2))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(float(wm["local_loss"]),
                               float(gm["local_loss"]), atol=1e-6)


def test_sparse_downlink_broadcasts_update_not_weights():
    """A top-k downlink must NOT hand clients a mostly-zero model: the
    broadcast stream compresses the model *update* against a mirror, so
    the decoded broadcast stays close to the true model."""
    bundle, fl = _tiny_setup()
    fl = dataclasses.replace(fl, downlink_codec="topk", topk_frac=0.05)
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    from repro.compress import make_codec
    up = IdentityCodec().bind(state["model"])
    down = make_codec("topk", topk_frac=0.05).bind(state["model"])
    comp = make_compressed_round_fn(bundle, fl, "client_parallel", up, down)
    ef = jax.tree.map(lambda z: jnp.stack([z] * 4), up.init_state())
    batches, sizes = _round_inputs(jax.random.PRNGKey(1))
    new_state, _, _, mirror = comp(state, batches, sizes, 0.05, ef,
                                   state["model"], jax.random.PRNGKey(2))
    # round 1: model == mirror, update is zero -> clients saw the full
    # model, not a 5%-sparse one
    for m, b in zip(jax.tree.leaves(state["model"]),
                    jax.tree.leaves(mirror)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(m), atol=1e-6)
    # server model stays full-precision (update applied to global, the
    # mirror stream tracks it)
    new_state2, _, _, mirror2 = comp(new_state, batches, sizes, 0.05, ef,
                                     mirror, jax.random.PRNGKey(3))
    nz = sum(int(jnp.sum(l != 0)) for l in jax.tree.leaves(new_state2["model"]))
    total = sum(l.size for l in jax.tree.leaves(new_state2["model"]))
    assert nz > 0.5 * total   # dense, not top-k-sparse


def test_mirror_stream_converges_to_static_target():
    """The stateless top-k mirror stream must converge to the model (an
    EF residual on top of the mirror gap double-counts dropped mass and
    provably diverges — the round fn therefore encodes statelessly)."""
    model = {"w": jax.random.normal(jax.random.PRNGKey(0), (100,))}
    c = TopKCodec(0.05, impl="jnp").bind(model)
    mirror = jax.tree.map(jnp.zeros_like, model)
    for _ in range(60):
        upd = jax.tree.map(lambda m, w: m - w, model, mirror)
        p, _ = c.encode(upd, c.init_state())   # stateless, as rounds.py does
        mirror = jax.tree.map(lambda w, d: w + d, mirror, c.decode(p))
    gap = float(jnp.max(jnp.abs(model["w"] - mirror["w"])))
    assert gap < 1e-5, gap


def test_error_feedback_converges_within_2x_rounds():
    """Top-k+EF on synthetic non-IID reaches the identity-codec loss
    milestone within 2x the rounds (the EF convergence guarantee)."""
    from repro.data.federated import FederatedDataset
    from repro.data.partition import artificial_noniid_partition
    from repro.data.synth import class_images
    from repro.fl.server import run_federated

    cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"],
                              conv_channels=(8, 16), fc_units=(64,),
                              dropout=0.0)
    bundle = make_bundle(cfg)
    x, y = class_images(24, seed=0, template_seed=0, noise=0.2)
    parts = artificial_noniid_partition(x, y, 8)
    xt, yt = class_images(8, seed=1, template_seed=0, noise=0.2)

    def rounds_to_loss(codec, rounds):
        data = FederatedDataset(parts, {"x": xt, "y": yt}, seed=7)
        fl = FLConfig(algorithm="fedavg", clients_per_round=4,
                      local_steps=4, local_batch=32, lr=0.06,
                      uplink_codec=codec, topk_frac=0.1)
        res = run_federated(bundle, fl, data, rounds=rounds, seed=0,
                            eval_every=10_000)
        for h in res.comm.history:
            if h["local_loss"] <= 1.2:
                return h["round"]
        return -1

    r_id = rounds_to_loss("identity", 12)
    assert r_id > 0, "identity baseline never hit the loss milestone"
    r_ef = rounds_to_loss("topk", 2 * r_id)
    assert 0 < r_ef <= 2 * r_id, (r_id, r_ef)
