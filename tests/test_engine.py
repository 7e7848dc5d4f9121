"""Engine equivalence: the superstep loop IS the reference loop.

The contract pinned here is the repo's strongest: for every execution mode
x codec combination, the chunked engine (``run_federated``) reproduces the
preserved pre-engine loop (``run_federated_reference``) *exactly* — final
global model bitwise-equal, CommLog history equal as Python objects
(bytes, local_loss and eval metrics included), and identical
checkpoint-resume behaviour.  K=1 bypasses ``lax.scan`` entirely; K=4
exercises the scan carry (global state + EF tree + mirror threading).

The SHARDED contract is one notch weaker by construction: the
client-parallel ``shard_map`` engine (client axis split over the mesh,
EF table row-sharded by cid) must be allclose to the single-device engine
— aggregation order changes, bits may not — with CommLog byte accounting
identical and metric trajectories equal to float tolerance.  It is pinned
two ways: in-process tests that run whenever the host is a forced
multi-device CPU (CI's forced-4-device job), and a subprocess grid that
forces 2- and 4-device hosts from inside a normal tier-1 run.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import CNN_CONFIGS
from repro.configs.base import FLConfig
from repro.data.federated import FederatedDataset
from repro.data.partition import iid_partition
from repro.data.synth import class_images
from repro.engine import chunk_schedule
from repro.fl.server import (_evaluate_eager, evaluate, run_federated,
                             run_federated_reference)
from repro.models.registry import make_bundle


_BUNDLE = None


def _bundle():
    global _BUNDLE
    if _BUNDLE is None:
        cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"],
                                  input_shape=(8, 8, 1), conv_channels=(4,),
                                  fc_units=(8,), dropout=0.0)
        _BUNDLE = make_bundle(cfg)
    return _BUNDLE


def _data(seed=3):
    x, y = class_images(12, n_classes=4, shape=(8, 8, 1), seed=0)
    return FederatedDataset(iid_partition(x, y, 4),
                            {"x": x[:16], "y": y[:16]}, seed=seed)


def _assert_same(ref, eng):
    for a, b in zip(jax.tree.leaves(ref.global_state),
                    jax.tree.leaves(eng.global_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.comm.history == eng.comm.history
    assert ref.comm.bytes_up == eng.comm.bytes_up
    assert ref.comm.bytes_down == eng.comm.bytes_down


FL_CASES = {
    "plain": dict(),
    "topk": dict(uplink_codec="topk", topk_frac=0.1),
    "quant+downtopk": dict(uplink_codec="int8", downlink_codec="topk",
                           topk_frac=0.1),
    "fusion-topk": dict(algorithm="fedfusion", fusion_op="conv",
                        uplink_codec="topk", topk_frac=0.1),
}


_REF_CACHE = {}


def _fl_for(case):
    kw = dict(FL_CASES[case])
    algo = kw.pop("algorithm", "fedavg")
    return FLConfig(algorithm=algo, clients_per_round=2, local_steps=2,
                    local_batch=4, lr=0.05, **kw)


def _reference(bundle, mode, case):
    if (mode, case) not in _REF_CACHE:
        _REF_CACHE[mode, case] = run_federated_reference(
            bundle, _fl_for(case), _data(), rounds=6, seed=1, eval_every=2,
            mode=mode)
    return _REF_CACHE[mode, case]


@pytest.mark.parametrize("mode", ["client_parallel", "client_sequential"])
@pytest.mark.parametrize("case", sorted(FL_CASES))
@pytest.mark.parametrize("chunk", [1, 4])
def test_engine_reproduces_reference(mode, case, chunk):
    """Chunked superstep == seed loop: model bitwise, history exactly."""
    bundle = _bundle()
    ref = _reference(bundle, mode, case)
    eng = run_federated(bundle, _fl_for(case), _data(), rounds=6, seed=1,
                        eval_every=2, mode=mode, superstep_rounds=chunk)
    _assert_same(ref, eng)


def test_engine_eval_every_round_in_scan():
    """eval_every=1 folds evaluation into the scan body; the per-round
    acc/loss trajectory still matches the reference exactly."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg", clients_per_round=2, local_steps=2,
                  local_batch=4, lr=0.05)
    ref = run_federated_reference(bundle, fl, _data(), rounds=5, seed=1,
                                  eval_every=1)
    eng = run_federated(bundle, fl, _data(), rounds=5, seed=1, eval_every=1,
                        superstep_rounds=4)
    _assert_same(ref, eng)
    assert all("acc" in h for h in eng.comm.history)


@pytest.mark.parametrize("impl,n_kernels", [("jnp", 0),
                                             ("pallas_interpret", 1)])
def test_eval_fn_dispatches_kernels_by_impl(impl, n_kernels):
    """The evaluator runs the deploy-time fusion operator with the engine's
    ``impl``, as training does: ``impl="jnp"`` traces no Pallas kernel."""
    from repro.core.rounds import init_global_state
    from repro.engine import make_eval_fn, pad_eval_batch
    bundle = _bundle()
    fl = FLConfig(algorithm="fedfusion", fusion_op="conv")
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    batch, mask = pad_eval_batch(_data().test_batch())
    jaxpr = jax.make_jaxpr(make_eval_fn(bundle, fl, impl=impl))(
        state, batch, mask)
    assert str(jaxpr).count("pallas_call") == n_kernels


@pytest.mark.parametrize("codec", ["identity", "topk"])
def test_engine_checkpoint_resume_matches_reference(tmp_path, codec):
    """Interrupt at round 4, resume to 8 — both loops land on the same
    state, and the engine restores the device-side EF tree from ef.npz."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg", clients_per_round=2, local_steps=2,
                  local_batch=4, lr=0.05, uplink_codec=codec, topk_frac=0.1)
    dr = _data()
    run_federated_reference(bundle, fl, dr, rounds=4, seed=1, eval_every=4,
                            checkpoint_dir=str(tmp_path / "ref"),
                            checkpoint_every=2)
    ref = run_federated_reference(bundle, fl, dr, rounds=8, seed=1,
                                  eval_every=4,
                                  checkpoint_dir=str(tmp_path / "ref"),
                                  checkpoint_every=2)
    de = _data()
    run_federated(bundle, fl, de, rounds=4, seed=1, eval_every=4,
                  checkpoint_dir=str(tmp_path / "eng"), checkpoint_every=2,
                  superstep_rounds=3)
    eng = run_federated(bundle, fl, de, rounds=8, seed=1, eval_every=4,
                        checkpoint_dir=str(tmp_path / "eng"),
                        checkpoint_every=2, superstep_rounds=3)
    _assert_same(ref, eng)
    assert ref.comm.rounds == eng.comm.rounds == 4  # only rounds 5..8 ran


def test_engine_callback_gets_per_round_state():
    """A callback forces one-round chunks and sees the same (round,
    metrics) sequence as the reference loop."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg", clients_per_round=2, local_steps=1,
                  local_batch=4, lr=0.05)

    def make_cb(store):
        def cb(r, state, metrics):
            store[r] = dict(metrics)
        return cb

    ref_seen, eng_seen = {}, {}
    run_federated_reference(bundle, fl, _data(), rounds=3, seed=1,
                            eval_every=1, callback=make_cb(ref_seen))
    run_federated(bundle, fl, _data(), rounds=3, seed=1, eval_every=1,
                  callback=make_cb(eng_seen), superstep_rounds=4)
    assert ref_seen == eng_seen
    assert sorted(ref_seen) == [0, 1, 2]


def test_engine_prefetch_off_identical():
    """prefetch=False (synchronous staging) changes nothing numerically."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg", clients_per_round=2, local_steps=1,
                  local_batch=4, lr=0.05)
    a = run_federated(bundle, fl, _data(), rounds=4, seed=1,
                      superstep_rounds=2, prefetch=True)
    b = run_federated(bundle, fl, _data(), rounds=4, seed=1,
                      superstep_rounds=2, prefetch=False)
    _assert_same(a, b)


def test_chunk_schedule_boundaries():
    """Chunks never cross eval or checkpoint boundaries."""
    sched = chunk_schedule(0, 20, 8, eval_every=5, ckpt_every=4)
    assert sched[0] == (0, 4)
    flat = [b for _, b in sched]
    assert all(b % 5 == 0 or b % 4 == 0 or b == 20 for b in flat)
    assert sched[-1][1] == 20
    # contiguous, in order
    assert all(sched[i][1] == sched[i + 1][0] for i in range(len(sched) - 1))
    # per-round mode (callback) degenerates to K=1
    assert chunk_schedule(2, 5, 8, per_round=True) == [(2, 3), (3, 4),
                                                       (4, 5)]
    # eval folded into the scan imposes no boundary
    assert chunk_schedule(0, 16, 8, eval_every=None) == [(0, 8), (8, 16)]


def test_engine_auto_chunk_rounds_identical():
    """superstep_rounds='auto' calibrates K on a cloned rng stream — the
    results must stay bitwise-equal to a fixed-K run and the choice lands
    in ServerResult.stats."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg", clients_per_round=2, local_steps=1,
                  local_batch=4, lr=0.05)
    fixed = run_federated(bundle, fl, _data(), rounds=4, seed=1,
                          eval_every=4, superstep_rounds=4)
    auto = run_federated(bundle, fl, _data(), rounds=4, seed=1,
                         eval_every=4, superstep_rounds="auto")
    _assert_same(fixed, auto)
    assert isinstance(auto.stats["chunk_rounds"], int)
    assert auto.stats["chunk_rounds"] >= 8


def test_engine_eval_overlap_identical():
    """Snapshot-based eval dispatch (overlap_eval) changes scheduling
    only: histories and final models match the non-overlapped run
    bitwise."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg", clients_per_round=2, local_steps=1,
                  local_batch=4, lr=0.05)
    a = run_federated(bundle, fl, _data(), rounds=6, seed=1, eval_every=2,
                      superstep_rounds=2, overlap_eval=True)
    b = run_federated(bundle, fl, _data(), rounds=6, seed=1, eval_every=2,
                      superstep_rounds=2, overlap_eval=False)
    _assert_same(a, b)
    assert a.stats["eval_overlap"] and not b.stats["eval_overlap"]


# ---------------------------------------------------------------------------
# Sharded engine: client-parallel shard_map over a forced host mesh
# ---------------------------------------------------------------------------

SHARDED_CASES = {
    "plain": ("client_parallel", dict()),
    "topk": ("client_parallel", dict(uplink_codec="topk", topk_frac=0.1)),
    "quant+downtopk": ("client_parallel",
                       dict(uplink_codec="int8", downlink_codec="topk",
                            topk_frac=0.1)),
    "fusion-topk": ("client_parallel",
                    dict(algorithm="fedfusion", fusion_op="conv",
                         uplink_codec="topk", topk_frac=0.1)),
    "topk-seq": ("client_sequential",
                 dict(uplink_codec="topk", topk_frac=0.1)),
}


def _sharded_fl(case):
    mode, kw = SHARDED_CASES[case]
    kw = dict(kw)
    algo = kw.pop("algorithm", "fedavg")
    return mode, FLConfig(algorithm=algo, clients_per_round=4,
                          local_steps=2, local_batch=4, lr=0.05, **kw)


def _sharded_data(seed=3):
    x, y = class_images(12, n_classes=4, shape=(8, 8, 1), seed=0)
    return FederatedDataset(iid_partition(x, y, 8),
                            {"x": x[:16], "y": y[:16]}, seed=seed)


def assert_results_close(single, sharded, rtol=2e-5, atol=1e-6):
    """Sharded-vs-single contract: model allclose, byte accounting exact,
    metric trajectory equal to float tolerance."""
    for a, b in zip(jax.tree.leaves(single.global_state),
                    jax.tree.leaves(sharded.global_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)
    assert len(single.comm.history) == len(sharded.comm.history)
    assert single.comm.bytes_up == sharded.comm.bytes_up
    assert single.comm.bytes_down == sharded.comm.bytes_down
    for h1, h2 in zip(single.comm.history, sharded.comm.history):
        assert set(h1) == set(h2)
        for k in h1:
            if isinstance(h1[k], float):
                np.testing.assert_allclose(h1[k], h2[k], rtol=1e-4,
                                           atol=1e-5)
            else:
                assert h1[k] == h2[k], k


_multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a forced multi-device host "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=N + "
           "REPRO_ALLOW_FORCED_DEVICES=1)")


@_multidevice
@pytest.mark.parametrize("case", sorted(SHARDED_CASES))
def test_sharded_engine_matches_single_device(case):
    from repro.launch.mesh import make_engine_mesh
    mode, fl = _sharded_fl(case)
    bundle = _bundle()
    mesh = make_engine_mesh()   # all forced devices on the data axis
    single = run_federated(bundle, fl, _sharded_data(), rounds=4, seed=1,
                           eval_every=2, mode=mode, superstep_rounds=2)
    sharded = run_federated(bundle, fl, _sharded_data(), rounds=4, seed=1,
                            eval_every=2, mode=mode, superstep_rounds=2,
                            mesh=mesh)
    assert_results_close(single, sharded)
    assert sharded.stats["client_shards"] == jax.device_count()


@_multidevice
def test_sharded_checkpoint_resume_row_sharded_ef(tmp_path):
    """Interrupt + resume with the EF table row-sharded by cid: the saved
    ef.npz assembles the global table, the resume re-shards it, and the
    two-phase run matches the single-device two-phase run."""
    from repro.launch.mesh import make_engine_mesh
    _, fl = _sharded_fl("topk")
    bundle = _bundle()

    def two_phase(mesh, d):
        run_federated(bundle, fl, _sharded_data(), rounds=4, seed=1,
                      eval_every=4, superstep_rounds=3, mesh=mesh,
                      checkpoint_dir=str(d), checkpoint_every=2)
        return run_federated(bundle, fl, _sharded_data(), rounds=8, seed=1,
                             eval_every=4, superstep_rounds=3, mesh=mesh,
                             checkpoint_dir=str(d), checkpoint_every=2)

    single = two_phase(None, tmp_path / "single")
    sharded = two_phase(make_engine_mesh(), tmp_path / "sharded")
    assert_results_close(single, sharded)


_SHARDED_GRID_SCRIPT = textwrap.dedent("""
    import sys
    import jax
    assert jax.device_count() == int(sys.argv[1]), jax.devices()
    from test_engine import (SHARDED_CASES, _assert_same, _bundle,
                             _sharded_data, _sharded_fl,
                             assert_results_close)
    from repro.fl.server import run_federated
    from repro.launch.mesh import make_engine_mesh

    mesh = make_engine_mesh()
    for case in sys.argv[2:]:
        mode, fl = _sharded_fl(case)
        single = run_federated(_bundle(), fl, _sharded_data(), rounds=4,
                               seed=1, eval_every=2, mode=mode,
                               superstep_rounds=2)
        sharded = run_federated(_bundle(), fl, _sharded_data(), rounds=4,
                                seed=1, eval_every=2, mode=mode,
                                superstep_rounds=2, mesh=mesh)
        assert_results_close(single, sharded)
        # the fused one-psum round must match the three-collective
        # oracle BITWISE (models and full histories, eval included)
        unfused = run_federated(_bundle(), fl, _sharded_data(), rounds=4,
                                seed=1, eval_every=2, mode=mode,
                                superstep_rounds=2, mesh=mesh,
                                fused_collective=False)
        _assert_same(unfused, sharded)
        print(f"case {case}: OK")
    print("SHARDED-OK")
""")


@pytest.mark.parametrize("n_devices,cases", [
    (2, ["plain", "topk", "topk-seq"]),
    (4, ["topk", "fusion-topk"]),
])
def test_sharded_equivalence_forced_host_mesh(n_devices, cases):
    """The tier-1-runnable form of the sharded grid: a subprocess forces an
    N-device CPU host (the flag must be set before jax initializes, hence
    the subprocess) and checks sharded == single-device per case."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    env = dict(os.environ)
    # drop any inherited force flag (e.g. from CI's forced-4-device job)
    # so the child sees exactly n_devices
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in t]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={n_devices}"])
    env["REPRO_ALLOW_FORCED_DEVICES"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [src, here] + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_GRID_SCRIPT, str(n_devices)] + cases,
        capture_output=True, text=True, env=env, timeout=1200)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED-OK" in out.stdout


def _forced_host_env(n_devices):
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    env = dict(os.environ)
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in t]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={n_devices}"])
    env["REPRO_ALLOW_FORCED_DEVICES"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [src, here] + env.get("PYTHONPATH", "").split(os.pathsep))
    return env


@_multidevice
@pytest.mark.parametrize("case", ["topk", "fusion-topk", "topk-seq"])
def test_sharded_fused_collective_bitwise(case):
    """Acceptance: the fused one-psum round == the three-collective
    oracle BITWISE — final model and full CommLog history (bytes,
    local_loss, eval metrics) — packing psum operands into one buffer is
    a latency change, never a numerics change."""
    from repro.launch.mesh import make_engine_mesh
    mode, fl = _sharded_fl(case)
    bundle = _bundle()
    mesh = make_engine_mesh()
    kw = dict(rounds=4, seed=1, eval_every=2, mode=mode,
              superstep_rounds=2, mesh=mesh)
    fused = run_federated(bundle, fl, _sharded_data(), fused_collective=True,
                          **kw)
    unfused = run_federated(bundle, fl, _sharded_data(),
                            fused_collective=False, **kw)
    _assert_same(unfused, fused)
    assert fused.stats["fused_collective"]
    assert not unfused.stats["fused_collective"]


@_multidevice
def test_sharded_eval_matches_replicated_eval():
    """Sharded evaluation (batch split + masked-sum psum) vs the
    replicated evaluator on the same mesh: training is untouched (models
    bitwise-equal) and the eval metrics agree to float tolerance (the
    split only reassociates the masked sums)."""
    from repro.launch.mesh import make_engine_mesh
    mode, fl = _sharded_fl("topk")
    bundle = _bundle()
    mesh = make_engine_mesh()
    kw = dict(rounds=4, seed=1, eval_every=1, mode=mode,
              superstep_rounds=2, mesh=mesh)
    shd = run_federated(bundle, fl, _sharded_data(), sharded_eval=True, **kw)
    repl = run_federated(bundle, fl, _sharded_data(), sharded_eval=False,
                         **kw)
    for a, b in zip(jax.tree.leaves(repl.global_state),
                    jax.tree.leaves(shd.global_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert_results_close(repl, shd)
    assert shd.stats["sharded_eval"] and not repl.stats["sharded_eval"]


@_multidevice
@pytest.mark.parametrize("resume_on_mesh", [False, True])
def test_sharded_checkpoint_cross_layout_resume(tmp_path, resume_on_mesh):
    """Resident-scratch-row round trip across layouts: a checkpoint saved
    from the sharded [N_loc+1] table restores into BOTH the compact
    single-device layout and the resident sharded layout (ef.npz stays
    format-compatible), and the resumed two-phase run matches the
    single-device two-phase oracle."""
    from repro.launch.mesh import make_engine_mesh
    _, fl = _sharded_fl("topk")
    bundle = _bundle()
    mesh = make_engine_mesh()
    d = tmp_path / "ckpt"
    kw = dict(seed=1, eval_every=4, superstep_rounds=3,
              checkpoint_dir=str(d), checkpoint_every=2)
    # phase 1 on the mesh -> ef.npz written from the resident layout
    run_federated(bundle, fl, _sharded_data(), rounds=4, mesh=mesh, **kw)
    # phase 2 restores into the other (or same) layout
    two_phase = run_federated(bundle, fl, _sharded_data(), rounds=8,
                              mesh=mesh if resume_on_mesh else None, **kw)
    oracle = run_federated(bundle, fl, _sharded_data(), rounds=4, seed=1,
                           eval_every=4, superstep_rounds=3,
                           checkpoint_dir=str(tmp_path / "o"),
                           checkpoint_every=2)
    oracle = run_federated(bundle, fl, _sharded_data(), rounds=8, seed=1,
                           eval_every=4, superstep_rounds=3,
                           checkpoint_dir=str(tmp_path / "o"),
                           checkpoint_every=2)
    assert_results_close(oracle, two_phase)


_ONE_PSUM_SCRIPT = textwrap.dedent("""
    import sys
    import jax
    import jax.numpy as jnp
    assert jax.device_count() == 2, jax.devices()
    from test_engine import _bundle, _sharded_fl
    from repro.analysis import count_collectives, round_body
    from repro.compress import make_codec
    from repro.core.rounds import init_global_state
    from repro.engine.sharded import client_sharding, make_sharded_superstep
    from repro.launch.mesh import make_engine_mesh

    mesh = make_engine_mesh()
    shard = client_sharding(mesh)
    mode, fl = _sharded_fl("topk")
    bundle = _bundle()
    uplink = make_codec(fl.uplink_codec, topk_frac=fl.topk_frac)
    downlink = make_codec(fl.downlink_codec)
    state = jax.eval_shape(lambda k: init_global_state(bundle, fl, k),
                           jax.random.PRNGKey(0))
    uplink.bind(state["model"])
    downlink.bind(state["model"])
    K, C, S, B = 4, fl.clients_per_round, fl.local_steps, fl.local_batch
    n_loc = 8 // shard.n_shards
    ef = [jax.ShapeDtypeStruct(
              ((n_loc + 1) * shard.n_shards,) + z.shape, z.dtype)
          for z in jax.eval_shape(uplink.init_state)]
    args = (state, ef, state["model"],
            {"x": jax.ShapeDtypeStruct((K, C, S, B, 8, 8, 1), jnp.float32),
             "y": jax.ShapeDtypeStruct((K, C, S, B), jnp.int32)},
            jax.ShapeDtypeStruct((K, C), jnp.float32),
            jax.ShapeDtypeStruct((K,), jnp.float32),
            jax.ShapeDtypeStruct((K, C), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    counts = {}
    for fused in (True, False):
        fn = make_sharded_superstep(bundle, fl, mode, K, mesh,
                                    uplink=uplink, downlink=downlink,
                                    fused_collective=fused)
        jaxpr = jax.make_jaxpr(fn)(*args)
        # repro.analysis.round_body: the outermost (K-round) scan body
        body = round_body(jaxpr)
        counts[fused] = (count_collectives(body), count_collectives(jaxpr))
    per_round, total = counts[True]
    assert per_round == 1, f"fused round body has {per_round} psums"
    # one prologue psum per chunk (round 0's EF gather + weight total)
    assert total == 2, f"fused superstep has {total} psums"
    assert counts[False][0] >= 3, counts  # the three-collective oracle
    print(f"fused: {per_round} psum/round ({total} total); "
          f"unfused round body: {counts[False][0]} psums")
    print("ONE-PSUM-OK")
""")


def test_fused_superstep_one_psum_per_round():
    """Acceptance: with fused_collective=True the compressed sharded
    round executes exactly ONE psum per round — asserted by counting psum
    eqns in the K-round scan body's jaxpr on a forced 2-device host (the
    chunk adds a single prologue psum)."""
    env = _forced_host_env(2)
    out = subprocess.run([sys.executable, "-c", _ONE_PSUM_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "ONE-PSUM-OK" in out.stdout


def test_jitted_evaluate_matches_eager():
    """The pad-and-mask jitted evaluator equals the uncompiled original."""
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg")
    from repro.core.rounds import init_global_state
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    batch = _data().test_batch()
    fast = evaluate(bundle, fl, state, batch)
    slow = _evaluate_eager(bundle, fl, state, batch)
    assert fast.keys() == slow.keys()
    for k in fast:
        np.testing.assert_allclose(fast[k], slow[k], rtol=1e-5, atol=1e-6)


def test_jitted_evaluate_respects_max_examples():
    bundle = _bundle()
    fl = FLConfig(algorithm="fedavg")
    from repro.core.rounds import init_global_state
    state = init_global_state(bundle, fl, jax.random.PRNGKey(0))
    batch = _data().test_batch()
    fast = evaluate(bundle, fl, state, batch, max_examples=8)
    slow = _evaluate_eager(bundle, fl, state, batch, max_examples=8)
    for k in fast:
        np.testing.assert_allclose(fast[k], slow[k], rtol=1e-5, atol=1e-6)


def test_pad_eval_batch_empty_raises():
    """Regression: an empty test batch used to produce bucket=1 with an
    all-false mask — metrics silently degenerate instead of erroring."""
    from repro.engine import pad_eval_batch
    empty = {"x": np.zeros((0, 8, 8, 1), np.float32),
             "y": np.zeros((0,), np.int32)}
    with pytest.raises(ValueError, match="0 examples"):
        pad_eval_batch(empty)


def test_pad_eval_batch_shard_divisible():
    """pad_eval_batch(shard=) rounds the bucket up to a multiple of the
    shard count; the extra rows are masked pad."""
    from repro.engine import pad_eval_batch
    batch = {"x": np.ones((5, 8, 8, 1), np.float32),
             "y": np.ones((5,), np.int32)}
    padded, mask = pad_eval_batch(batch, shard=3)
    assert padded["x"].shape[0] % 3 == 0
    assert int(np.sum(np.asarray(mask))) == 5
    # unsharded: unchanged power-of-two bucketing
    padded, mask = pad_eval_batch(batch)
    assert padded["x"].shape[0] == 8


def test_masked_metric_sums_match_means():
    """The psum-able masked sums divide back to the masked means."""
    import jax.numpy as jnp
    from repro.core import (masked_accuracy, masked_accuracy_sum,
                            masked_cross_entropy, masked_cross_entropy_sum)
    key = jax.random.PRNGKey(1)
    logits = jax.random.normal(key, (8, 5))
    labels = jax.random.randint(key, (8,), 0, 5)
    mask = jnp.arange(8) < 6
    c, w = masked_accuracy_sum(logits, labels, mask)
    assert float(w) == 6.0
    np.testing.assert_allclose(float(c) / float(w),
                               float(masked_accuracy(logits, labels, mask)),
                               rtol=1e-6)
    ce, w2 = masked_cross_entropy_sum(logits, labels, mask)
    np.testing.assert_allclose(
        float(ce) / float(w2),
        float(masked_cross_entropy(logits, labels, mask)), rtol=1e-6)


def _pump_comm():
    from repro.fl.comm import CommLog
    return CommLog().bind_sizes({"model": {"w": np.zeros(4, np.float32)}})


def test_metrics_pump_empty_stack():
    """Regression: an empty metrics stack raised bare StopIteration from
    ``next(iter(stack.values()))`` inside the worker drain."""
    from repro.engine import MetricsPump
    comm = _pump_comm()
    pump = MetricsPump(comm, 2)
    pump.submit({}, None)                      # no per-round metrics
    pump.submit({}, {"acc": np.float32(0.5)})  # eval-only chunk
    pump.close()
    assert comm.rounds == 1                    # the eval-only round logged
    assert comm.history[-1]["acc"] == 0.5


def test_metrics_pump_verbose_nonfloat(capsys):
    """Regression: verbose formatting crashed with ``:.4f`` on non-float
    metric values (e.g. a per-class vector)."""
    from repro.engine import MetricsPump
    comm = _pump_comm()
    pump = MetricsPump(comm, 2, verbose=True)
    pump.submit({"local_loss": np.ones((2,), np.float32),
                 "per_class": np.arange(6, dtype=np.int32).reshape(2, 3)},
                None)
    pump.close()
    out = capsys.readouterr().out
    assert comm.rounds == 2
    assert "local_loss=1.0000" in out
    assert "per_class=" in out
    np.testing.assert_array_equal(comm.history[-1]["per_class"], [3, 4, 5])


def test_ef_scratch_row_layout_round_trip():
    """checkpoint.io strip/insert are exact inverses and keep ef.npz in
    the compact [N, ...] layout; scratch rows restore as zeros at the end
    of every shard block."""
    from repro.checkpoint.io import insert_scratch_rows, strip_scratch_rows
    rng = np.random.default_rng(0)
    compact = {"a": rng.normal(size=(8, 5)).astype(np.float32),
               "b": rng.normal(size=(8,)).astype(np.float32)}
    for s in (1, 2, 4):
        resident = insert_scratch_rows(compact, s)
        for k in compact:
            assert resident[k].shape[0] == 8 + s
        back = strip_scratch_rows(resident, s)
        for k in compact:
            np.testing.assert_array_equal(back[k], compact[k])
    blocks = insert_scratch_rows(compact, 4)["a"].reshape(4, 3, 5)
    assert (blocks[:, -1] == 0).all()


def test_masked_metrics_ignore_padding():
    """Masked accuracy/CE on a padded batch == plain metrics unpadded."""
    import jax.numpy as jnp
    from repro.core import (accuracy, cross_entropy, masked_accuracy,
                            masked_cross_entropy)
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (6, 5))
    labels = jax.random.randint(key, (6,), 0, 5)
    pad_logits = jnp.concatenate([logits, 100 * jnp.ones((2, 5))])
    pad_labels = jnp.concatenate([labels, jnp.zeros((2,), labels.dtype)])
    mask = jnp.arange(8) < 6
    np.testing.assert_allclose(
        float(masked_accuracy(pad_logits, pad_labels, mask)),
        float(accuracy(logits, labels)), rtol=1e-6)
    np.testing.assert_allclose(
        float(masked_cross_entropy(pad_logits, pad_labels, mask)),
        float(cross_entropy(logits, labels)), rtol=1e-5)
