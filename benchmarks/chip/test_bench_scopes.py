"""The per-layer readers of the program's round scopes and counters, on
hand-made traces and on recorded v5e traces of the program before and
after it named the four parts of a round.

The traces are written back into the ``XSpace`` protobuf a profiler
writes (``test_bench_trace.encode``), so the readers run on the same
reading as in a traced run.
"""
from __future__ import annotations

import json
import os
import types

import pytest

import scope_time
import spec
import xtrace
from test_bench_trace import encode, op

HERE = os.path.dirname(os.path.abspath(__file__))

PARTS = ("local_train", "codec", "aggregate", "eval")
SCOPE_READERS = {"local_train": "local_train_ms_per_round",
                 "codec": "codec_scope_ms_per_round",
                 "aggregate": "aggregate_ms_per_round",
                 "eval": "eval_ms_per_round"}


def _obs(reduced, traced_rounds=1.0, rounds=1, stats=None):
    return types.SimpleNamespace(trace=reduced, traced_rounds=traced_rounds,
                                 rounds=rounds, stats=stats or {})


def _load(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        fix = json.load(f)
    raw = encode({0: [tuple(e) for e in fix["device"]]},
                 {"python": [tuple(h[1:]) for h in fix["host"]]},
                 fix["metadata"])
    return fix, xtrace.reduce_space(raw, [0])


@pytest.fixture(scope="module")
def unscoped_trace():
    """The program before the scopes (the benchmark's first recording)."""
    return _load("trace_mnist_fedmmd_topk.json")


@pytest.fixture(scope="module")
def scoped_trace():
    """A round and a half of the program with its scopes, recorded on a
    v5e."""
    return _load("trace_mnist_fedmmd_topk_scopes.json")


def _outermost_ns(fix, match):
    """Device ns of the fixture's events whose name stack ``match``es, each
    with what nests in it and none counted twice: by hand, from the
    intervals, apart from ``xtrace``'s nesting."""
    hits = sorted((s, s + d) for n, s, d in fix["device"]
                  if match(fix["metadata"].get(n, {}).get("tf_op", "")))
    total, end = 0, None
    for s, e in hits:
        if end is None or s >= end:
            total += e - s
            end = e
        else:
            end = max(end, e)
    return total


def _tf_op_under(name):
    u = scope_time.under(name)
    return lambda tf_op: u(types.SimpleNamespace(tf_op=tf_op))


# ---------------------------------------------------------------------------
# hand-made traces
# ---------------------------------------------------------------------------

def test_scope_readers_by_hand():
    """Each part's reader takes the ops whose name stack holds its name
    as a whole segment, bare or inside a transform, with what nests in
    them counted once; a name inside a longer one does not count."""
    base = "jit(superstep)/while/body/closed_call/"
    evs = [(op("conv.1", "convolution"), 10, 20),        # local_train fwd
           (op("conv.2", "convolution"), 40, 10),        # local_train bwd
           (op("sort.3", "sort"), 60, 30),               # codec
           (op("sc.4", "scatter"), 95, 5),               # no name stack
           (op("add.5", "add"), 110, 4),                 # aggregate
           (op("cond.6", "conditional"), 120, 20),       # eval, holding
           (op("dot.7", "dot"), 125, 10),                # ... this one
           (op("x.8"), 150, 6)]                          # evaluation_x
    meta = {
        op("conv.1", "convolution"): {
            "tf_op": base + "vmap(local_train)/jvp()/conv_general_dilated:"},
        op("conv.2", "convolution"): {
            "tf_op": base + "vmap(local_train)/transpose(jvp())/conv:"},
        op("sort.3", "sort"): {"tf_op": base + "vmap(codec)/top_k:"},
        op("add.5", "add"): {"tf_op": base + "aggregate/add:"},
        op("cond.6", "conditional"): {"tf_op": base + "eval/cond:"},
        op("dot.7", "dot"): {"tf_op": base + "eval/jit(_dot)/dot_general:"},
        op("x.8"): {"tf_op": base + "evaluation_x/codecs/add:"}}
    r = xtrace.reduce_space(encode({0: evs}, {}, meta), [0])
    obs = _obs(r, traced_rounds=2.0)
    want = {"local_train": 30, "codec": 30, "aggregate": 4, "eval": 20}
    for part, ns in want.items():
        assert spec.reader(SCOPE_READERS[part]).read(obs) == \
            pytest.approx(1000 * ns * 1e-9 / 2.0), part
    # the parts and what lies under none (the bare scatter, the longer
    # name) add up to the busy time
    under = [scope_time.under(p) for p in PARTS]
    rest = r.op_seconds(lambda o: not any(u(o) for u in under))
    assert rest == pytest.approx(11e-9)
    assert sum(want.values()) * 1e-9 + rest == pytest.approx(r.busy_s())


def test_scope_names_hold_no_older_reader_fragment():
    """The four names match none of what the older readers look for, so
    those read the scoped program as they read the one before."""
    codec = spec.reader("codec_ms_per_round")
    frags = codec.NAMES + ("gram_sum", "repro/compress/")
    assert not any(f in p for f in frags for p in PARTS)


def test_scope_readers_read_nothing_without_scopes(unscoped_trace):
    """A trace of the program before the scopes: each reader finds
    nothing and returns None."""
    _, r = unscoped_trace
    for name in SCOPE_READERS.values():
        assert spec.reader(name).read(_obs(r)) is None


@pytest.mark.parametrize("part", PARTS)
def test_recorded_scope_readers(scoped_trace, part):
    """On the scoped program's recorded round each part's reader reads
    the device time of the events under the part's name."""
    fix, r = scoped_trace
    ns = _outermost_ns(fix, _tf_op_under(part))
    assert ns > 0
    got = spec.reader(SCOPE_READERS[part]).read(_obs(r, traced_rounds=2.0))
    assert got == pytest.approx(1000 * ns * 1e-9 / 2.0, rel=1e-9)


def test_recorded_parts_and_the_rest_tile_busy(scoped_trace):
    """No recorded op lies under two parts, and the four parts and what
    lies under none add up to the busy time."""
    fix, r = scoped_trace
    for n, _, _ in fix["device"]:
        tf_op = fix["metadata"].get(n, {}).get("tf_op", "")
        assert sum(_tf_op_under(p)(tf_op) for p in PARTS) <= 1, tf_op
    under = [scope_time.under(p) for p in PARTS]
    parts = sum(r.op_seconds(u) for u in under)
    rest = r.op_seconds(lambda o: not any(u(o) for u in under)
                        and not any(u(a) for u in under
                                    for a in _ancestors(o)))
    assert parts + rest == pytest.approx(r.busy_s(), rel=1e-9)


def _ancestors(o):
    p = o.parent
    while p is not None:
        yield p
        p = p.parent


def test_recorded_ops_lie_in_their_parts(scoped_trace):
    """The codec's top-k, local training's convolutions and gram_sum
    calls, and eval's convolutions carry the part the program put them
    in."""
    _, r = scoped_trace
    ops = r.ops[0]
    codec, local, ev = (scope_time.under(p)
                        for p in ("codec", "local_train", "eval"))
    top_k = [o for o in ops if "/top_k" in o.tf_op]
    assert any(o.opcode == "sort" for o in top_k)
    assert all(codec(o) for o in top_k)
    convs = [o for o in ops if "conv_general_dilated" in o.tf_op]
    assert any(local(o) for o in convs) and any(ev(o) for o in convs)
    assert all(local(o) or ev(o) for o in convs)
    grams = [o for o in ops if "gram_sum" in o.tf_op]
    assert grams and all(local(o) for o in grams)


def test_recorded_ops_the_compiler_made_lie_under_no_part(scoped_trace):
    """Ops the TPU compiler makes anew carry no name stack of their own,
    only the round loop's: the top-k decode's flat scatter, the sort of
    its indices, and the relayouts around the EF kernels."""
    _, r = scoped_trace
    made = [o for o in r.ops[0]
            if o.shape.startswith(("f32[16056320]", "f32[13,8,12544,128]",
                                   "f32[104,12544,128]"))
            or (o.opcode == "sort" and o.shape.startswith("(s32[802820]"))]
    assert {o.opcode for o in made} >= {"fusion", "copy", "pad", "sort"}
    for o in made:
        assert o.tf_op.rstrip(":") == "jit(superstep)/while", o.tf_op


def test_scope_readers_read_nothing_without_a_trace():
    for name in SCOPE_READERS.values():
        assert spec.reader(name).read(_obs(None)) is None


@pytest.mark.parametrize("name,stats,rounds,want", [
    ("stage_sample_ms_per_round", {"stage_sample_s": 0.8}, 16, 50.0),
    ("stage_sample_ms_per_round", {}, 16, None),
    ("fit_start_s", {"fit_start_s": 3.25}, 16, 3.25),
    ("fit_start_s", {}, 16, None),
])
def test_program_counter_readers(name, stats, rounds, want):
    got = spec.reader(name).read(_obs(None, rounds=rounds, stats=stats))
    assert got == (pytest.approx(want) if want is not None else None)
