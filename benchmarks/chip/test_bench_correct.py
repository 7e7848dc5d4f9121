"""``correct`` against the reference, its control and the faults, on the CPU.

Each one-chip cell with its images, batch and (at most 12) local steps,
through narrower layers (convs of 8 and 16 channels, dense layers of 64)
and on a federation a test can hold (10 clients of 100, cohort 2, a test
set of 500), judged by the cell's own limits (``limits/<cell>.json``):

* the program as it is passes (on the CPU it computes what the reference
  computes, to float32 rounding);
* the control -- the reference in bfloat16 in the program's place --
  fails;
* a run of the harness with the timed path broken underneath fails: a
  step that returns its state unchanged, and half of each batch left out
  of the loss with the mean taken over the rest.

The harness's look for a chip is skipped by handing it the CPU device.
"""
from __future__ import annotations

import io
import json
import os

import pytest

import reference
import run
import spec
import verdict

ONE_CHIP = [w["name"] for w in spec.benchmark()["workloads"]
            if w["chips"] == 1]
SEED = 2 ** 31 + 3


def small(name):
    """The cell's images, batch and local steps (12 at most) through
    narrower convolutions (8 and 16 channels) and dense layers (64), on
    a federation a test can hold."""
    cell = spec.Cell.load(name)
    cell.model.update(conv_channels=[8, 16],
                      fc_units=[64] * len(cell.model["fc_units"]))
    fed = cell.traffic["federation"]
    fed.update(n_clients=10, per_client=100, n_test=500)
    fl = cell.traffic["fl"]
    fl.update(clients_per_round=2, local_steps=min(fl["local_steps"], 12))
    cell.traffic["eval_examples"] = fed["n_test"]
    return cell


def harness_run(cell):
    import jax
    out, err = io.StringIO(), io.StringIO()
    result = run.run_cell(cell, SEED, 0.5, 0, devices=jax.devices()[:1],
                          out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    # the numbers compared are the last lines of standard error
    names = [line.split()[1] for line in err.getvalue().splitlines()]
    assert names == list(result["checks"])
    return result


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_program_is_correct(name):
    result = harness_run(small(name))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 16


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_in_the_programs_place_is_not_correct(name):
    cell = small(name)
    parts, test = __import__("gen").federation(
        cell.model, cell.traffic["federation"], SEED)
    k = cell.traffic["superstep_rounds"]
    ref0, ref_final, ref_hist = reference.run_reference(
        cell.model, cell.traffic, parts, test, SEED, k,
        reference.for_config(cell.config))
    _, ctl_final, ctl_hist = reference.run_reference(
        cell.model, cell.traffic, parts, test, SEED, k, reference.CONTROL)
    values, _ = verdict.numbers(ctl_hist, ctl_final, ref0, ref_hist,
                                ref_final)
    ok, checks = verdict.judge(values, verdict.load_limits(name))
    assert not ok, checks


def _unchanged_state(monkeypatch):
    """Every superstep hands back the global state it was given."""
    from repro.engine import engine

    def wrap(build):
        def builder(*a, **kw):
            step = build(*a, **kw)

            def superstep(state, *rest):
                out = step(state, *rest)
                return (state,) + tuple(out[1:])
            return superstep
        return builder

    for name in ("make_plain_superstep", "make_compressed_superstep"):
        monkeypatch.setattr(engine, name, wrap(getattr(engine, name)))


def _half_batch(monkeypatch):
    """The loss sees the first half of each batch, its mean taken there."""
    from repro.fl.api import plugins
    ce = plugins.cross_entropy

    def half(logits, labels):
        n = labels.shape[0] // 2
        return ce(logits[:n], labels[:n])

    monkeypatch.setattr(plugins, "cross_entropy", half)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["unchanged_state", "half_batch"])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    result = harness_run(small(name))
    assert not result["correct"], result["checks"]


def test_reference_draws_the_programs_initial_weights():
    import jax
    import numpy as np

    from repro.configs.base import CNNConfig, FLConfig
    from repro.core.rounds import init_global_state
    from repro.models.registry import make_bundle
    for name in ONE_CHIP:
        cell = spec.Cell.load(name)
        m = cell.model
        cfg = CNNConfig(name="c", input_shape=tuple(m["input_shape"]),
                        conv_channels=tuple(m["conv_channels"]),
                        pool_size=m["pool_size"],
                        pool_stride=m["pool_stride"],
                        fc_units=tuple(m["fc_units"]),
                        n_classes=m["n_classes"])
        fl = FLConfig(**{k: tuple(v) if isinstance(v, list) else v
                         for k, v in cell.traffic["fl"].items()})
        theirs = reference.leaf_dict(init_global_state(
            make_bundle(cfg), fl, jax.random.PRNGKey(SEED)))
        mine = reference.leaf_dict(reference.init_state(m, SEED))
        assert sorted(mine) == sorted(theirs)
        for k in mine:
            assert np.array_equal(mine[k], theirs[k]), (name, k)


def test_no_chip_no_result(capsys):
    assert run.main(["--workload", ONE_CHIP[0], "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("mix", CELLS)
def test_reference_follows_the_program_on_every_traffic_mix(mix):
    """Every cell's traffic mix and configuration: on the CPU, where both
    compute in float32, the reference follows the program's first step
    to rounding."""
    import jax
    cell = spec.Cell.load(mix)
    traffic = cell.traffic
    cell.model.update(input_shape=[12, 12, cell.model["input_shape"][-1]],
                      conv_channels=[4, 8],
                      fc_units=[16] * len(cell.model["fc_units"]))
    traffic["federation"].update(n_clients=10, per_client=40, n_test=100)
    traffic["fl"].update(clients_per_round=min(
        traffic["fl"]["clients_per_round"], 10), local_batch=8,
        local_steps=2)
    traffic["eval_examples"] = 100
    tight = {"loss1_gap": 1e-5, "eval1_gap": 1e-5, "change_gap": 1e-5}
    out, err = io.StringIO(), io.StringIO()
    result = run.run_cell(cell, SEED, 0.5, 0, devices=jax.devices()[:1],
                          limits=tight, out=out, err=err)
    assert result["correct"], result["checks"]


FOUR_CHIPS = r'''
import json, sys
sys.path[:0] = [{here!r}, {src!r}]
import jax
import run, spec
with open({traffic!r}) as f:
    traffic = json.load(f)
with open({config!r}) as f:
    config = json.load(f)
cell = spec.Cell("x4", 4, config, traffic, [], [])
cell.model.update(input_shape=[12, 12, 1], conv_channels=[4, 8],
                  fc_units=[16])
traffic["federation"].update(n_clients=8, per_client=40, n_test=100)
traffic["fl"].update(clients_per_round=8, local_batch=8, local_steps=2)
traffic["eval_examples"] = 100
if {fault!r}:
    from repro.engine import superstep
    superstep.fused_psum = lambda tree, shard: tree
tight = {{"loss1_gap": 1e-5, "eval1_gap": 1e-5, "change_gap": 1e-5}}
result = run.run_cell(cell, 5, 0.5, 0, devices=jax.devices()[:4],
                      limits=tight)
print("CORRECT", result["correct"])
'''


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_four_chip_path_on_virtual_devices(fault):
    """The harness on the engine's four-chip path (clients split over a
    mesh, one packed psum a round) on four virtual CPU devices: it follows
    the reference, and with the exchange between the chips left out it
    does not."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(spec.HERE)), "src")
    code = FOUR_CHIPS.format(
        here=spec.HERE, src=src, fault=fault,
        traffic=os.path.join(spec.HERE, "traffic",
                             "paper_mnist.fedmmd.topk.json"),
        config=os.path.join(spec.HERE, "configs", "cnn_mnist.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert f"CORRECT {not fault}" in proc.stdout, proc.stdout[-3000:]
