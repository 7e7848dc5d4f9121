"""Observability contracts (repro.obs).

The load-bearing guarantee: telemetry is *bitwise-invisible*.  A
telemetry-on engine run must produce the exact final model, the exact
CommLog byte accounting and the exact non-telemetry metric history of a
telemetry-off run — on a single device for every mode x codec case, and
on a forced multi-device sharded mesh, where the tap sums additionally
must NOT add any collective beyond the PR 5 single fused psum
(jaxpr-asserted).  The rest pins the host-side machinery: RunLog JSONL
round-trip and span nesting, the zero-allocation disabled path, the
MetricsPump exception-abort cleanup, the non-finite metric warning, and
the CommLog record serialization the report CLI consumes.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.comm import CommLog
from repro.fl.server import run_federated
from repro.obs import (NULL_RUNLOG, NullRunLog, RunLog, as_runlog,
                       build_report, json_safe, make_telemetry,
                       registered_taps, render)
from repro.obs.telemetry import (ClientTapCtx, TelemetryTap, _TAPS,
                                 register_tap)

from test_engine import FL_CASES, _bundle, _data, _fl_for, _forced_host_env


# ---------------------------------------------------------------------------
# Tentpole: telemetry-on == telemetry-off, bitwise
# ---------------------------------------------------------------------------

def _strip_tele(history):
    return [{k: v for k, v in h.items() if not k.startswith("tele/")}
            for h in history]


def _assert_invisible(off, on):
    """Telemetry-on == telemetry-off: model bitwise, bytes exact, and the
    history identical once the tele/ series are removed."""
    for a, b in zip(jax.tree.leaves(off.global_state),
                    jax.tree.leaves(on.global_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert off.comm.bytes_up == on.comm.bytes_up
    assert off.comm.bytes_down == on.comm.bytes_down
    assert off.comm.history == _strip_tele(on.comm.history)


_TELE_GRID = [("client_parallel", c) for c in sorted(FL_CASES)] \
    + [("client_sequential", "topk")]


@pytest.mark.parametrize("mode,case", _TELE_GRID)
def test_telemetry_bitwise_invisible(mode, case):
    bundle = _bundle()
    kw = dict(rounds=4, seed=1, eval_every=2, mode=mode, superstep_rounds=2)
    off = run_federated(bundle, _fl_for(case), _data(), **kw)
    on = run_federated(bundle, _fl_for(case), _data(), telemetry=True, **kw)
    _assert_invisible(off, on)
    assert on.stats["telemetry"] and not off.stats["telemetry"]
    tele = {k for h in on.comm.history for k in h if k.startswith("tele/")}
    if case == "plain":
        assert {"tele/update_norm", "tele/weight_total"} <= tele
    else:
        assert {"tele/delta_norm_pre", "tele/delta_norm_post",
                "tele/compress_err", "tele/weight_total"} <= tele
    if case in ("topk", "fusion-topk"):    # stateful uplink -> EF taps
        assert {"tele/ef_norm", "tele/ef_delta_ratio"} <= tele


def test_telemetry_tap_subset_and_chunk_invariance():
    """An explicit tap-name list selects only those series, and the tele
    values are chunk-size-invariant like every other engine metric."""
    bundle = _bundle()
    kw = dict(rounds=4, seed=1, eval_every=2)
    a = run_federated(bundle, _fl_for("topk"), _data(), telemetry=("ef",),
                      superstep_rounds=1, **kw)
    b = run_federated(bundle, _fl_for("topk"), _data(), telemetry=("ef",),
                      superstep_rounds=4, **kw)
    tele = {k for h in a.comm.history for k in h if k.startswith("tele/")}
    assert tele == {"tele/ef_norm", "tele/ef_delta_ratio"}
    assert a.comm.history == b.comm.history


_SHARDED_TELE_SCRIPT = textwrap.dedent("""
    import jax
    import numpy as np
    assert jax.device_count() == 2, jax.devices()
    from test_engine import _bundle, _sharded_data, _sharded_fl
    from test_obs import _assert_invisible
    from repro.fl.server import run_federated
    from repro.launch.mesh import make_engine_mesh

    mesh = make_engine_mesh()
    for case in ("plain", "topk", "topk-seq"):
        mode, fl = _sharded_fl(case)
        kw = dict(rounds=4, seed=1, eval_every=2, mode=mode,
                  superstep_rounds=2, mesh=mesh)
        off = run_federated(_bundle(), fl, _sharded_data(), **kw)
        on = run_federated(_bundle(), fl, _sharded_data(), telemetry=True,
                           **kw)
        _assert_invisible(off, on)
        tele = {k for h in on.comm.history for k in h
                if k.startswith("tele/")}
        assert tele, case
        # the per-shard count proves the sums crossed the psum: each of
        # the 2 shards contributed half the round's clients
        assert on.comm.history[0]["tele/clients_per_shard"] \\
            == fl.clients_per_round / 2, on.comm.history[0]
        assert on.comm.history[0]["tele/clients"] == fl.clients_per_round
        print(f"case {case}: OK")
    print("SHARDED-TELE-OK")
""")


def test_sharded_telemetry_bitwise_invisible_forced_host():
    """The sharded form of the tentpole contract, on a forced 2-device
    host: telemetry-on == telemetry-off bitwise under shard_map (fused
    one-psum rounds), with the tap sums provably psum'd across shards."""
    env = _forced_host_env(2)
    out = subprocess.run([sys.executable, "-c", _SHARDED_TELE_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED-TELE-OK" in out.stdout


_TELE_ONE_PSUM_SCRIPT = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    assert jax.device_count() == 2, jax.devices()
    from test_engine import _bundle, _sharded_fl
    from repro.analysis import count_collectives, round_body
    from repro.compress import make_codec
    from repro.core.rounds import init_global_state
    from repro.engine.sharded import client_sharding, make_sharded_superstep
    from repro.launch.mesh import make_engine_mesh
    from repro.obs.telemetry import make_telemetry

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

    tele = make_telemetry("compressed", n_clients=C,
                          n_shards=shard.n_shards,
                          available=frozenset(("ef",)))
    assert tele is not None and len(tele.taps) >= 3
    fn = make_sharded_superstep(bundle, fl, mode, K, mesh, uplink=uplink,
                                downlink=downlink, fused_collective=True,
                                telemetry=tele)
    jaxpr = jax.make_jaxpr(fn)(*args)
    body = round_body(jaxpr)
    per_round, total = count_collectives(body), count_collectives(jaxpr)
    assert per_round == 1, f"telemetry round body has {per_round} psums"
    assert total == 2, f"telemetry superstep has {total} psums"
    print(f"telemetry-on fused: {per_round} psum/round ({total} total)")
    print("TELE-ONE-PSUM-OK")
""")


def test_sharded_telemetry_adds_no_collective():
    """Acceptance: with every compressed tap active, the fused sharded
    round STILL executes exactly one psum per round (the tap sums ride
    the PR 5 packed collective) — same jaxpr counting as the PR 5 test."""
    env = _forced_host_env(2)
    out = subprocess.run([sys.executable, "-c", _TELE_ONE_PSUM_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "TELE-ONE-PSUM-OK" in out.stdout


# ---------------------------------------------------------------------------
# Tap registry
# ---------------------------------------------------------------------------

def test_make_telemetry_selection():
    t = make_telemetry("plain", n_clients=4)
    assert {tap.name for tap in t.taps} == {"update", "weights"}
    t = make_telemetry("compressed", n_clients=4)
    assert {tap.name for tap in t.taps} == {"delta", "weights"}
    t = make_telemetry("compressed", n_clients=4,
                       available=frozenset(("ef",)))
    assert {tap.name for tap in t.taps} == {"delta", "ef", "weights"}
    assert t.round_ctx.n_clients == 4
    # nothing applies -> None (treated as telemetry-off)
    assert make_telemetry("plain", taps=("ef",)) is None
    with pytest.raises(KeyError):
        make_telemetry("plain", taps=("nonsense",))


def test_register_tap_plugin():
    class LossTap(TelemetryTap):
        name = "losscheck"
        kinds = ("plain", "compressed")
        requires = ("loss",)

        def client_sums(self, ctx):
            return {"sum": jnp.asarray(ctx.loss, jnp.float32)}

        def finish(self, summed, ctx):
            return {"loss_mean": summed["losscheck.sum"] / ctx.n_clients}

    register_tap(LossTap())
    try:
        assert "losscheck" in registered_taps()
        t = make_telemetry("plain", n_clients=2, taps=("losscheck",))
        sums = t.client_sums(ClientTapCtx(loss=jnp.float32(3.0)))
        assert set(sums) == {"losscheck.sum"}
        out = t.finish({"losscheck.sum": jnp.float32(6.0)})
        assert float(out["tele/loss_mean"]) == 3.0
    finally:
        _TAPS.pop("losscheck", None)
    with pytest.raises(ValueError):
        register_tap(TelemetryTap())    # default name rejected


def test_registered_taps_ride_engine(tmp_path):
    """A registered plugin tap's series shows up in the engine history."""
    class NexTap(TelemetryTap):
        name = "nexmax"
        kinds = ("plain",)
        requires = ("n_examples",)

        def client_sums(self, ctx):
            return {"sum": jnp.asarray(ctx.n_examples, jnp.float32)}

        def finish(self, summed, ctx):
            return {"nex_sum": summed["nexmax.sum"]}

    register_tap(NexTap())
    try:
        res = run_federated(_bundle(), _fl_for("plain"), _data(), rounds=2,
                            seed=1, eval_every=2, superstep_rounds=2,
                            telemetry=("nexmax",))
        assert all("tele/nex_sum" in h for h in res.comm.history)
    finally:
        _TAPS.pop("nexmax", None)


# ---------------------------------------------------------------------------
# RunLog
# ---------------------------------------------------------------------------

def test_runlog_jsonl_roundtrip_and_nesting(tmp_path):
    path = str(tmp_path / "log" / "run.jsonl")
    rl = RunLog(path)
    rl.event("run.start", rounds=3, arr=np.int64(7))
    with rl.span("outer", tag="a"):
        with rl.span("inner"):
            pass
    rl.counter("queue.wait_s", np.float32(0.25))
    rl.warning("metrics.nonfinite", round=2, keys=["acc"])
    rl.close()

    recs = rl.records()
    # spans record at exit: inner closes before outer
    assert [r["kind"] for r in recs] == ["event", "span", "span",
                                        "counter", "event"]
    inner = next(r for r in recs if r.get("name") == "inner")
    outer = next(r for r in recs if r.get("name") == "outer")
    assert inner["parent"] == outer["id"]       # nesting recorded
    assert outer["parent"] is None
    assert outer["tag"] == "a"
    assert inner["dur"] <= outer["dur"]
    warn = next(r for r in recs if r.get("level") == "warning")
    assert warn["name"] == "metrics.nonfinite" and warn["round"] == 2

    # streaming file == in-memory records == load()
    assert RunLog.load(path) == recs
    # every record is already plain JSON (numpy converted at emit time)
    json.dumps(recs)

    path2 = str(tmp_path / "resaved.jsonl")
    rl.save(path2)
    assert RunLog.load(path2) == recs


def test_runlog_thread_local_nesting():
    """Spans on another thread must not parent under this thread's."""
    import threading
    rl = RunLog()
    got = {}

    def worker():
        with rl.span("worker.span"):
            pass
        got["done"] = True

    with rl.span("main.span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert got["done"]
    w = next(r for r in rl.records() if r["name"] == "worker.span")
    assert w["parent"] is None


def test_null_runlog_zero_allocation():
    """The disabled path hands back ONE shared span instance — no per-call
    allocation in the hot loop — and records nothing, at every span site
    the engine has, while no profiler session records (JAX loaded)."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert as_runlog(None) is NULL_RUNLOG
    assert isinstance(as_runlog(NULL_RUNLOG), NullRunLog)
    s1 = NULL_RUNLOG.span("chunk.dispatch", r0=0, r1=8)
    s2 = NULL_RUNLOG.span("anything.else")
    assert s1 is s2                      # the shared _NULL_SPAN singleton
    for name in _ENGINE_SPANS + ("prefetch.stage.fence",):
        assert NULL_RUNLOG.span(name, r0=0, r1=8) is s1, name
    with s1:
        pass
    NULL_RUNLOG.event("e")
    NULL_RUNLOG.counter("c", 1)
    NULL_RUNLOG.warning("w")
    assert NULL_RUNLOG.records() == []
    assert not NULL_RUNLOG.enabled and NULL_RUNLOG.path is None


# ---------------------------------------------------------------------------
# engine spans in any jax.profiler trace
# ---------------------------------------------------------------------------

# every span a fit with paged EF and checkpoints emits on the CPU (the
# staging fence, prefetch.stage.fence, runs on accelerators only)
_ENGINE_SPANS = ("fit.prologue", "ef.init", "prefetch.stage",
                 "prefetch.stage.sample", "prefetch.stage.put",
                 "chunk.dispatch", "eval.dispatch", "checkpoint.save",
                 "ef.page.gather", "ef.page.writeback")


def _host_events(trace_dir):
    """(name, start_s, dur_s, stats) of the trace's host events that the
    engine emits: its spans and its ``superstep`` steps."""
    import glob
    pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(pb)
    ours = set(_ENGINE_SPANS) | {"prefetch.stage.fence", "superstep"}
    return [(ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9,
             {k: v for k, v in ev.stats})
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name in ours]


@pytest.fixture(scope="module")
def traced_fits(tmp_path_factory):
    """One short fit under ``jax.profiler.start_trace`` with no RunLog,
    then one with a RunLog: (events, None) and (events, records)."""
    out = {}
    for name, runlog in (("null", None), ("log", RunLog())):
        base = tmp_path_factory.mktemp(name)
        jax.profiler.start_trace(str(base / "trace"))
        try:
            res = run_federated(_bundle(), _fl_for("topk"), _data(),
                                rounds=4, seed=1, eval_every=2,
                                superstep_rounds=2, ef_store="host",
                                checkpoint_dir=str(base / "ckpt"),
                                checkpoint_every=2, runlog=runlog)
        finally:
            jax.profiler.stop_trace()
        out[name] = (_host_events(str(base / "trace")),
                     runlog.records() if runlog is not None else None,
                     res.stats)
    return out


def test_engine_spans_in_profiler_trace_without_runlog(traced_fits):
    """With ``runlog=None`` and no ``profile_dir``, an operator's trace
    holds every engine span and one ``superstep`` step per chunk."""
    events, _, _ = traced_fits["null"]
    names = {e[0] for e in events}
    assert set(_ENGINE_SPANS) <= names, set(_ENGINE_SPANS) - names
    steps = sorted(st["step_num"] for n, _, _, st in events
                   if n == "superstep")
    assert steps == [0, 1]


def test_runlog_spans_match_trace_events_on_one_clock(traced_fits):
    """With a RunLog, each span record is exactly one trace event (same
    name, its ``id`` as metadata), the durations agree within 0.5 ms, and
    one constant maps every record's ``t0`` onto its event's start within
    0.5 ms."""
    events, records, _ = traced_fits["log"]
    spans = [r for r in records if r["kind"] == "span"]
    assert {r["name"] for r in spans} >= set(_ENGINE_SPANS)
    by_id = {}
    for name, start, dur, stats in events:
        if name in _ENGINE_SPANS and "id" in stats:
            by_id.setdefault(int(stats["id"]), []).append(
                (name, start, dur))
    assert len(by_id) == len(spans)
    offsets = []
    for r in spans:
        match = by_id.get(r["id"], [])
        assert [m[0] for m in match] == [r["name"]], (r, match)
        _, start, dur = match[0]
        assert abs(dur - r["dur"]) < 5e-4, (r, dur)
        offsets.append(start - r["t0"])
    mid = (max(offsets) + min(offsets)) / 2
    assert all(abs(o - mid) < 5e-4 for o in offsets), offsets


def test_staging_parts_tile_prefetch_stage(traced_fits):
    """``prefetch.stage.sample`` / ``.put`` (/ ``.fence``) are the
    children of each ``prefetch.stage`` and add up to it within 5%; the
    fit's ``stage_*_s`` totals are their sums."""
    _, records, stats = traced_fits["log"]
    spans = [r for r in records if r["kind"] == "span"]
    stages = [r for r in spans if r["name"] == "prefetch.stage"]
    assert len(stages) == 2
    kids_total = {"sample": 0.0, "put": 0.0, "fence": 0.0}
    for st in stages:
        kids = [r for r in spans if r["parent"] == st["id"]
                and r["name"].startswith("prefetch.stage.")]
        assert {k["name"] for k in kids} >= {"prefetch.stage.sample",
                                             "prefetch.stage.put"}
        assert sum(k["dur"] for k in kids) == pytest.approx(st["dur"],
                                                            rel=0.05)
        for k in kids:
            kids_total[k["name"].rsplit(".", 1)[1]] += k["dur"]
    for part_name, total in kids_total.items():
        assert stats[f"stage_{part_name}_s"] == pytest.approx(
            total, abs=2e-3), part_name


def test_fit_prologue_and_start(traced_fits):
    """``fit.prologue`` runs from the fit's entry to its loop's first
    chunk, with the EF table's ``ef.init`` inside it, and
    ``fit_start_s`` reaches the end of the first ``chunk.dispatch``."""
    _, records, stats = traced_fits["log"]
    spans = [r for r in records if r["kind"] == "span"]
    pro = next(r for r in spans if r["name"] == "fit.prologue")
    init = next(r for r in spans if r["name"] == "ef.init")
    first = min((r for r in spans if r["name"] == "chunk.dispatch"),
                key=lambda r: r["t0"])
    assert pro["parent"] is None and init["parent"] == pro["id"]
    assert pro["t0"] <= init["t0"] and \
        init["t0"] + init["dur"] <= pro["t0"] + pro["dur"]
    assert pro["t0"] + pro["dur"] <= first["t0"] + 1e-3
    assert stats["fit_start_s"] == pytest.approx(
        first["t0"] + first["dur"] - pro["t0"], abs=5e-3)


def test_runlog_module_imports_no_jax():
    """``repro.obs.runlog`` itself (loaded without its package) imports
    stdlib and numpy only: it finds JAX's profiler in ``sys.modules``."""
    import repro.obs.runlog as mod
    code = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "runlog", {mod.__file__!r})
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        with m.NULL_RUNLOG.span("chunk.dispatch"):
            pass
        rl = m.RunLog()
        with rl.span("chunk.dispatch"):
            pass
        assert "jax" not in sys.modules, "jax imported"
        print("NO-JAX-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert "NO-JAX-OK" in out.stdout, out.stderr


def test_as_runlog_path(tmp_path):
    p = str(tmp_path / "x.jsonl")
    rl = as_runlog(p)
    assert isinstance(rl, RunLog) and rl.path == p
    rl.event("e")
    rl.close()
    assert RunLog.load(p)[0]["name"] == "e"
    assert as_runlog(rl) is rl


def test_json_safe():
    assert json_safe(np.float32(1.5)) == 1.5
    assert json_safe(np.int64(3)) == 3
    assert json_safe(np.bool_(True)) == 1
    assert json_safe(jnp.arange(3)) == [0, 1, 2]
    assert json_safe(np.float64(2.0)) == 2.0
    assert json_safe({"a": (np.int32(1), None)}) == {"a": [1, None]}
    assert isinstance(json_safe(object()), str)   # fallback, never raises


# ---------------------------------------------------------------------------
# MetricsPump: context-manager lifecycle
# ---------------------------------------------------------------------------

def _comm():
    return CommLog().bind_sizes(
        {"model": {"w": np.zeros(4, np.float32)}})


def test_metrics_pump_clean_exit_drains():
    from repro.engine.metrics import MetricsPump
    comm = _comm()
    with MetricsPump(comm, 2) as pump:
        pump.submit({"local_loss": jnp.asarray([1.0, 2.0])})
    assert comm.rounds == 2
    assert [h["local_loss"] for h in comm.history] == [1.0, 2.0]


def test_metrics_pump_abort_on_exception():
    """Regression: an exception inside the pump context must cancel the
    pending fetches and retire the executor WITHOUT blocking — the old
    close() path would drain (and potentially hang on) device futures
    mid-unwind."""
    from repro.engine.metrics import MetricsPump
    comm = _comm()
    pump = MetricsPump(comm, 2)
    with pytest.raises(RuntimeError, match="boom"):
        with pump:
            pump.submit({"local_loss": jnp.asarray([1.0, 2.0])})
            raise RuntimeError("boom")
    assert not pump._pending                 # queue dropped, not drained
    with pytest.raises(RuntimeError):        # executor is shut down
        pump._pool.submit(lambda: None)


def test_metrics_pump_nonfinite_warning():
    """A NaN/inf metric value still lands in the history untouched (the
    reference-equality contract) but emits a structured warning with its
    round index and key names."""
    from repro.engine.metrics import MetricsPump
    comm = _comm()
    rl = RunLog()
    with MetricsPump(comm, 2, runlog=rl) as pump:
        pump.submit({"local_loss": jnp.asarray([1.0, jnp.nan]),
                     "aux": jnp.asarray([jnp.inf, 2.0])})
    warns = [r for r in rl.records() if r.get("level") == "warning"]
    assert [w["round"] for w in warns] == [1, 2]
    assert warns[0]["keys"] == ["aux"]
    assert warns[1]["keys"] == ["local_loss"]
    assert math.isnan(comm.history[1]["local_loss"])   # value untouched


# ---------------------------------------------------------------------------
# CommLog records + report
# ---------------------------------------------------------------------------

def test_commlog_to_records_save_roundtrip(tmp_path):
    comm = _comm()
    comm.log_round(None, 2, {"acc": np.float32(0.5),
                             "tele/ef_norm": np.float32(0.1)})
    comm.log_round(None, 2, {"acc": np.float32(0.75)})
    recs = comm.to_records()
    json.dumps(recs)                         # plain JSON end to end
    assert [r["kind"] for r in recs] == ["round", "round", "summary"]
    assert recs[0]["acc"] == 0.5 and recs[0]["round"] == 1
    assert recs[-1]["rounds"] == 2
    assert recs[-1]["bytes_up"] == comm.bytes_up

    path = str(tmp_path / "comm.jsonl")
    comm.save(path)
    with open(path) as f:
        loaded = [json.loads(line) for line in f]
    assert loaded == recs


def test_report_from_engine_run(tmp_path):
    """End-to-end: instrumented run -> JSONL artifacts -> report dict
    with the round-time breakdown and telemetry trends."""
    path = str(tmp_path / "run.jsonl")
    res = run_federated(_bundle(), _fl_for("topk"), _data(), rounds=4,
                        seed=1, eval_every=2, superstep_rounds=2,
                        telemetry=True, runlog=path,
                        checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=2)
    assert res.stats["runlog"] == path
    recs = RunLog.load(path)
    report = build_report(recs, res.comm.to_records())
    rt = report["round_time"]
    assert rt["chunks"] == 2 and rt["compiles"] >= 1
    assert rt["dispatch_s"] > 0 and rt["wall_s"] > 0
    assert rt["checkpoint_s"] > 0
    assert "metrics_drain_s" in rt and "prefetch_stall_s" in rt
    assert "eval.dispatch" in report["spans"]
    assert "prefetch.stage" in report["spans"]
    assert report["bytes"]["rounds"] == 4
    assert report["bytes"]["uplink_compression"] > 1   # topk uplink
    assert "tele/ef_norm" in report["telemetry"]
    text = render(report)
    assert "round-time breakdown" in text and "tele/ef_norm" in text


def test_report_ef_page_section_paged_only(tmp_path):
    """A cohort-paged run's report carries the ef_page accounting (rows
    gathered/written back, gather + stall seconds in the round-time
    breakdown); a dense run's report omits the section entirely."""
    reports = {}
    for store in ("host", "device"):
        path = str(tmp_path / f"run_{store}.jsonl")
        res = run_federated(_bundle(), _fl_for("topk"), _data(), rounds=4,
                            seed=1, eval_every=0, superstep_rounds=2,
                            runlog=path, ef_store=store)
        assert res.stats["ef_store"] == store
        reports[store] = build_report(RunLog.load(path),
                                      res.comm.to_records())

    ef = reports["host"]["ef_page"]
    # 2 chunks x 2 rounds x 2 clients, deduped per chunk: every unique
    # gathered row comes back as a writeback row
    rows = ef["hits"] + ef["misses"]
    assert 0 < rows <= 8 and ef["writeback_rows"] == rows
    assert ef["writeback_count"] == 2
    assert ef["gather_count"] == 2 and ef["gather_s"] >= 0
    assert 0 <= ef["hit_rate"] <= 1
    rt = reports["host"]["round_time"]
    assert "ef_gather_s" in rt and "ef_stall_s" in rt
    text = render(reports["host"])
    assert "ef page store" in text and f"written back: {rows} rows" in text

    assert "ef_page" not in reports["device"]
    assert "ef page store" not in render(reports["device"])


def test_report_empty_inputs():
    assert build_report(None, None) == {}
    assert render({}) == "(empty report)"
