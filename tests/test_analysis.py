"""repro.analysis: pass registry, jaxpr substrate, invariant passes and
seeded-violation mutation tests (the analyzer must CATCH planted bugs —
a green run proves nothing if the passes are vacuous)."""
import contextlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from test_engine import _forced_host_env

from repro.analysis import (AnalysisPass, Finding, SuperstepSpec,
                            count_collectives, default_matrix,
                            lower_superstep, make_pass, register_pass,
                            registered_passes, round_body, run_analysis,
                            scan_bodies)
from repro.analysis import registry as _registry

BUILTIN_PASSES = ("collective-bytes", "collectives", "donation", "dtype",
                  "host-sync", "scopes", "source-lint")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_builtin_passes_registered():
    assert registered_passes() == BUILTIN_PASSES
    for name in BUILTIN_PASSES:
        p = make_pass(name)
        assert p.name == name
        assert p.scope in ("lowered", "source")
        assert p.description


def test_registry_round_trip_and_validation():
    @register_pass
    class _TmpPass(AnalysisPass):
        name = "tmp-test-pass"
        scope = "source"

        def run(self, target):
            return [self.finding("x", "y")]

    try:
        assert "tmp-test-pass" in registered_passes()
        f = make_pass("tmp-test-pass").run(None)[0]
        assert isinstance(f, Finding) and f.pass_name == "tmp-test-pass"
    finally:
        _registry._PASSES.pop("tmp-test-pass")

    with pytest.raises(KeyError):
        make_pass("no-such-pass")
    with pytest.raises(ValueError, match="non-empty"):
        register_pass(type("Nameless", (AnalysisPass,), {}))
    with pytest.raises(ValueError, match="scope"):
        register_pass(type("BadScope", (AnalysisPass,),
                           {"name": "bad-scope", "scope": "nope"}))
    with pytest.raises(ValueError, match="already registered"):
        register_pass(type("Dup", (AnalysisPass,),
                           {"name": "collectives", "scope": "lowered"}))
    with pytest.raises(TypeError):
        register_pass(object)


# ---------------------------------------------------------------------------
# Jaxpr substrate
# ---------------------------------------------------------------------------

def test_count_collectives_and_round_body():
    def f(x):
        def inner(c, t):
            def innermost(c2, t2):
                return c2 * t2, t2
            c2, _ = jax.lax.scan(innermost, c, jnp.arange(3.0))
            return c2 + t, t

        return jax.lax.scan(inner, x, jnp.arange(4.0))

    jaxpr = jax.make_jaxpr(f)(0.0)
    assert count_collectives(jaxpr) == 0
    assert len(scan_bodies(jaxpr)) == 2
    body = round_body(jaxpr)   # depth picks the OUTER scan (length 4)
    assert len(scan_bodies(body)) == 1
    assert round_body(jax.make_jaxpr(jnp.sin)(0.0)) is None


def test_count_collectives_sees_nested_psum():
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.engine.sharded import _unchecked_shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def f(x):
        def body(c, _):
            return c + jax.lax.psum(c, "data"), c
        return jax.lax.scan(body, x, None, length=3)

    wrapped = _unchecked_shard_map(f, mesh, P(), P())
    jaxpr = jax.make_jaxpr(wrapped)(jnp.float32(1.0))
    assert count_collectives(jaxpr) == 1
    assert count_collectives(jaxpr, names=("psum",)) == 1
    assert count_collectives(jaxpr, names=("all_gather",)) == 0
    assert count_collectives(round_body(jaxpr)) == 1


# ---------------------------------------------------------------------------
# Known-good unsharded points: every lowered pass is clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["plain", "topk"])
def test_unsharded_superstep_clean(codec):
    low = lower_superstep(SuperstepSpec(codec=codec))
    for name in ("collectives", "host-sync", "dtype"):
        findings = make_pass(name).run(low)
        assert not findings, (name, [str(f) for f in findings])


def test_unsharded_compiled_passes_clean():
    low = lower_superstep(SuperstepSpec(codec="topk"))
    for name in ("donation", "collective-bytes"):
        findings = make_pass(name).run(low)
        assert not findings, (name, [str(f) for f in findings])


def test_runner_report():
    rep = run_analysis([SuperstepSpec(codec="plain")],
                       passes=["collectives", "host-sync", "source-lint"])
    assert rep.ok
    assert set(rep.points) == {"client_parallel/plain/unsharded",
                               "src/repro"}
    js = rep.to_json()
    assert js["ok"] and js["n_points"] == 2 and js["findings"] == []


def test_default_matrix_presets():
    quick = default_matrix("quick")
    full = default_matrix("full")
    assert len({s.point for s in quick}) == len(quick)
    assert len({s.point for s in full}) == len(full)
    assert set(quick) <= set(full)
    assert any(s.sharded and not s.fused for s in quick)
    assert any(s.ef_store == "host" for s in quick)
    assert any(s.controller != "static" for s in quick)
    unsharded = default_matrix("quick", sharded=False)
    assert unsharded and all(not s.sharded for s in unsharded)


# ---------------------------------------------------------------------------
# Seeded violations (in-process, unsharded)
# ---------------------------------------------------------------------------

def test_mutation_host_callback_caught():
    def add_cb(fn):
        def g(*args):
            jax.debug.callback(lambda: None)
            return fn(*args)
        return g

    low = lower_superstep(SuperstepSpec(codec="topk"), inner_wrap=add_cb)
    findings = make_pass("host-sync").run(low)
    assert findings, "host-sync pass missed a planted debug callback"
    assert any("debug_callback" in f.message for f in findings)


def test_mutation_f64_leaf_caught():
    def add_f64(fn):
        def g(*args):
            leaves, td = jax.tree.flatten(fn(*args))
            poisoned = jnp.asarray(leaves[0], jnp.float64) * 1.000001
            leaves[0] = poisoned.astype(leaves[0].dtype)
            return jax.tree.unflatten(td, leaves)
        return g

    with jax.enable_x64(True):
        low = lower_superstep(SuperstepSpec(codec="topk"),
                              inner_wrap=add_f64)
        findings = make_pass("dtype").run(low)
    assert findings, "dtype pass missed a planted float64 value"
    assert any("float64" in f.message for f in findings)


def test_mutation_broken_donation_caught():
    from repro.engine.superstep import donation_argnums
    low = lower_superstep(SuperstepSpec(codec="topk"), donate=())
    _ = low.compiled_text       # compile WITHOUT any donation...
    # ...then claim the engine's donations
    low.donate_argnums = donation_argnums(
        compressed=True, participation=False, controller=False)
    findings = make_pass("donation").run(low)
    assert findings, "donation pass missed donation being dropped"
    assert any("aliases 0 buffer" in f.message for f in findings)


@pytest.mark.parametrize("spec", [
    SuperstepSpec(codec="plain", participation=True),
    SuperstepSpec(codec="topk", participation=True, controller="ef_ratio"),
], ids=lambda s: s.point)
def test_donation_argnums_all_usable(spec):
    """The engine donates the same argnums on every backend, and each of
    them is taken over by an output: the widest signatures compile here
    with every donated leaf aliased and no "not usable" warning, which is
    what the chip compile must show too."""
    low = lower_superstep(spec)
    findings = make_pass("donation").run(low)
    assert not findings, [str(f) for f in findings]
    assert not [w for w in low.compile_warnings if "donat" in w.lower()]


def test_mutation_unusable_staged_donation_caught():
    """Donating the staged chunk (batches / sizes / cids / round_idx), as
    the engine once did off-CPU, leaves buffers no output can take: XLA
    warns and the donation pass reports it."""
    from repro.engine.superstep import donation_argnums
    donate = donation_argnums(compressed=True) + (3, 4, 6, 7)
    low = lower_superstep(SuperstepSpec(codec="topk"), donate=donate)
    findings = make_pass("donation").run(low)
    assert any("donat" in f.message.lower() for f in findings), \
        [str(f) for f in findings]


def test_mutation_fake_wire_model_caught():
    low = lower_superstep(SuperstepSpec(codec="topk"))
    low.wire_up = low.ideal_model_bytes * 2     # codec "expands" the wire
    findings = make_pass("collective-bytes").run(low)
    assert any("above the ideal" in f.message for f in findings)
    low2 = lower_superstep(SuperstepSpec(codec="topk",
                                         controller="ef_ratio"))
    low2.level_bytes = tuple(reversed(low2.level_bytes))
    findings = make_pass("collective-bytes").run(low2)
    assert any("not ascending" in f.message for f in findings)


# ---------------------------------------------------------------------------
# Round-part scopes (repro.obs.scopes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", default_matrix("full", sharded=False),
                         ids=lambda s: s.point)
def test_round_body_under_one_scope(spec):
    """Every equation of the round body, backward included, lies under
    exactly one of local_train / codec / aggregate / eval."""
    findings = make_pass("scopes").run(lower_superstep(spec))
    assert not findings, [str(f) for f in findings]


def _eval_superstep_jaxprs(codec):
    """The round body of a superstep with eval folded into the scan, and
    the boundary evaluator (``jit_eval``) alone."""
    from repro.analysis import analysis_bundle, fl_for
    from repro.analysis.lower import INPUT_SHAPE
    from repro.compress import make_codec
    from repro.engine.evaljit import make_eval_fn
    from repro.engine.superstep import (abstract_superstep_args,
                                        make_compressed_superstep,
                                        make_plain_superstep)
    bundle = analysis_bundle()
    fl = fl_for(SuperstepSpec(codec=codec))
    eval_fn = make_eval_fn(bundle, fl)
    test = ({"x": jax.ShapeDtypeStruct((16,) + INPUT_SHAPE, jnp.float32),
             "y": jax.ShapeDtypeStruct((16,), jnp.int32)},
            jax.ShapeDtypeStruct((16,), jnp.bool_))
    up = down = None
    if fl.compressed:
        up, down = make_codec(fl.uplink_codec, topk_frac=fl.topk_frac), \
            make_codec(fl.downlink_codec)
        model = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        up.bind(model)
        down.bind(model)
        fn = make_compressed_superstep(bundle, fl, "client_parallel", 3, up,
                                       down, eval_fn=eval_fn)
    else:
        fn = make_plain_superstep(bundle, fl, "client_parallel", 3,
                                  eval_fn=eval_fn)
    args = abstract_superstep_args(bundle, fl, 3, cohort=4, uplink=up,
                                   ef_rows=8, input_shape=INPUT_SHAPE)
    step = jax.make_jaxpr(fn)(*args, *test)
    boundary = jax.make_jaxpr(eval_fn)(args[0], *test)
    return round_body(step), boundary


@pytest.mark.parametrize("codec", ["plain", "topk"])
def test_eval_under_eval_scope(codec):
    """Eval folded into the scan and the boundary evaluator alike run
    under ``eval``: the body stays under one part per equation, and the
    evaluator's forward convolutions are ``eval``'s."""
    from repro.analysis.jaxprs import leaf_eqns_with_name_stacks
    from repro.analysis.passes import parts_of, scope_findings
    body, boundary = _eval_superstep_jaxprs(codec)
    assert scope_findings(body) == []
    in_body = {frozenset(parts_of(st)) for st, e in
               leaf_eqns_with_name_stacks(body)
               if e.primitive.name == "conv_general_dilated"}
    assert frozenset({"eval"}) in in_body
    assert frozenset({"local_train"}) in in_body
    stacks = [parts_of(st) for st, _ in
              leaf_eqns_with_name_stacks(boundary)]
    assert stacks and all(p == {"eval"} for p in stacks)


def test_mutation_unscoped_local_training_caught(monkeypatch):
    """A round whose local training lost its scope is caught: its
    equations sit under no part and its convolutions in the wrong one."""
    from repro.obs import scopes
    real = jax.named_scope
    monkeypatch.setattr(
        scopes.jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name == scopes.LOCAL_TRAIN
                      else real(name)))
    findings = make_pass("scopes").run(
        lower_superstep(SuperstepSpec(codec="topk")))
    msgs = " ".join(f.message for f in findings)
    assert "under none of" in msgs and "conv_general_dilated" in msgs


# ---------------------------------------------------------------------------
# Sharded: known-good + seeded violations under forced 2 devices
# ---------------------------------------------------------------------------

_SHARDED_ANALYSIS_SCRIPT = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    assert jax.device_count() == 2, jax.devices()
    from repro.analysis import SuperstepSpec, lower_superstep, make_pass

    spec = SuperstepSpec(codec="topk", sharded=True)

    # known good: every lowered pass is clean on the fused sharded point
    low = lower_superstep(spec)
    for name in ("collectives", "host-sync", "dtype", "donation",
                 "collective-bytes", "scopes"):
        fs = make_pass(name).run(low)
        assert not fs, (name, [str(f) for f in fs])

    # every sharded point's round body lies under the round's scopes
    from repro.analysis import default_matrix
    for sp in default_matrix("full", sharded=True):
        fs = make_pass("scopes").run(lower_superstep(sp))
        assert not fs, (sp.point, [str(f) for f in fs])

    # seeded: an EXTRA psum smuggled into the superstep body
    def add_psum(fn):
        def g(*args):
            out = fn(*args)
            extra = jax.lax.psum(jnp.float32(1.0), "data")
            leaves, td = jax.tree.flatten(out)
            leaves = ([leaves[0] + (extra * 0).astype(leaves[0].dtype)]
                      + leaves[1:])
            return jax.tree.unflatten(td, leaves)
        return g
    low2 = lower_superstep(spec, inner_wrap=add_psum)
    fs = make_pass("collectives").run(low2)
    assert any("3 collective equations" in f.message for f in fs), \\
        [str(f) for f in fs]

    # seeded: compile without donation, then claim the engine's argnums
    from repro.engine.superstep import donation_argnums
    low3 = lower_superstep(spec, donate=())
    _ = low3.compiled_text
    low3.donate_argnums = donation_argnums(
        compressed=True, participation=False, controller=False)
    fs = make_pass("donation").run(low3)
    assert any("aliases 0 buffer" in f.message for f in fs), \\
        [str(f) for f in fs]

    # seeded: a second psum inside the ROUND body via a host callback-free
    # wrap is not reachable from outside the scan, but a non-psum
    # collective at superstep level must also trip the flavour check
    def add_gather(fn):
        def g(*args):
            out = fn(*args)
            extra = jax.lax.all_gather(jnp.float32(1.0), "data")
            leaves, td = jax.tree.flatten(out)
            leaves = ([leaves[0] + (extra.sum() * 0).astype(leaves[0].dtype)]
                      + leaves[1:])
            return jax.tree.unflatten(td, leaves)
        return g
    low4 = lower_superstep(spec, inner_wrap=add_gather)
    fs = make_pass("collectives").run(low4)
    assert any("non-psum" in f.message for f in fs), [str(f) for f in fs]
    print("SHARDED-ANALYSIS-OK")
""")


def test_sharded_passes_and_mutations():
    """Acceptance: on a forced 2-device host every lowered pass is green
    for the fused sharded topk point, and planted violations (extra
    psum, non-psum collective, dropped donation) are each caught."""
    env = _forced_host_env(2)
    out = subprocess.run([sys.executable, "-c", _SHARDED_ANALYSIS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED-ANALYSIS-OK" in out.stdout


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_list_and_source_pass():
    from repro.analysis.cli import main
    assert main(["--list-passes"]) == 0
    assert main(["--passes", "source-lint", "--quiet"]) == 0
    assert main(["--passes", "no-such-pass", "--quiet"]) == 2


def test_cli_unsharded_scope(tmp_path):
    from repro.analysis.cli import main
    import json
    report = tmp_path / "report.json"
    rc = main(["--scope", "unsharded", "--passes",
               "collectives,host-sync,dtype", "--quiet",
               "--report", str(report)])
    assert rc == 0
    data = json.loads(report.read_text())
    assert data["ok"] and data["n_points"] >= 5
