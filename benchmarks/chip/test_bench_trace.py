"""The trace reduction on a recorded v5e trace and on hand-made ones.

The fixtures are written back into the ``XSpace`` protobuf a profiler
writes (``encode`` below), so the test runs the same reading
(``jax.profiler.ProfileData`` plus the metadata parser) as a traced run.
"""
from __future__ import annotations

import json
import os

import pytest

import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# an XSpace writer (the protobuf wire format)
# ---------------------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, lines, metadata=None):
    """lines: {line name: [(event name, start_ns, duration_ns)]};
    metadata: {event name: {stat name: str}}."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    stat_ids = {"tf_op": 1, "source_stack": 2}
    body = _field(2, name)
    for line_name, evs in lines.items():
        line = _field(2, line_name) + _field(3, 0)
        for n, start, dur in evs:
            line += _field(4, _field(1, ids[n]) + _field(2, start * 1000)
                           + _field(3, dur * 1000))
        body += _field(3, line)
    for n, i in ids.items():
        meta = _field(1, i) + _field(2, n)
        for key, val in (metadata or {}).get(n, {}).items():
            meta += _field(5, _field(1, stat_ids[key]) + _field(5, val))
        body += _field(4, _field(1, i) + _field(2, meta))
    for key, i in stat_ids.items():
        body += _field(5, _field(1, i) + _field(2, _field(1, i)
                                                + _field(2, key)))
    return _field(1, body)


def encode(device_lines, host_lines, metadata):
    """device_lines: {chip id: [events]}; host_lines: {line: [events]}."""
    raw = b""
    for chip, evs in device_lines.items():
        raw += _plane(f"/device:TPU:{chip}", {"XLA Ops": evs}, metadata)
    return raw + _plane("/host:CPU", host_lines)


def op(name, opcode="fusion", shape="f32[8]"):
    return f"%{name} = {shape}{{0}} {opcode}(f32[8]{{0}} %p)"


# ---------------------------------------------------------------------------
# hand-made traces
# ---------------------------------------------------------------------------

def test_busy_idle_and_exclusive_time_by_hand():
    # a loop [5, 100) holding ops [10, 30) and [40, 50); lone ops
    # [120, 150) and [200, 210): the traced window runs from the first op
    # to the last, 205 ns, busy 135, idle gaps 50 and 20
    evs = [(op("while.1", "while"), 5, 95), (op("a.1"), 10, 20),
           (op("sort.2", "sort"), 40, 10), (op("b.3"), 120, 30),
           (op("c.4"), 200, 10)]
    meta = {op("sort.2", "sort"): {"tf_op": "jit(superstep)/vmap()/top_k:"}}
    raw = encode({0: evs}, {"python": [("np.asarray(jax.Array)", 145, 55),
                                       ("chunk.dispatch", 100, 15)]}, meta)
    r = xtrace.reduce_space(raw, [0])
    assert r.span_s() == pytest.approx(205e-9)
    assert r.busy_s() == pytest.approx(135e-9)
    # the loop with its body, and (in the ranking) its own 95 - 20 - 10
    assert r.op_seconds(lambda o: o.opcode == "while") == \
        pytest.approx(95e-9)
    assert r.op_seconds(lambda o: True) == pytest.approx(135e-9)
    assert r.op_seconds(lambda o: "top_k" in o.tf_op) == pytest.approx(10e-9)
    assert r.top_ops(1) == [["while f32[8]", pytest.approx(65e-9)]]
    gaps = r.idle_gaps(5)
    assert gaps == [["host: np.asarray(jax.Array)", pytest.approx(50e-9)],
                    ["host: chunk.dispatch", pytest.approx(20e-9)]]


def test_all_reduce_time_takes_the_busiest_chip():
    ar = op("all-reduce.3", "all-reduce")
    chips = {0: [(ar, 0, 40), (op("x.1"), 50, 10)],
             1: [(ar, 0, 60), (op("x.1"), 70, 10)]}
    r = xtrace.reduce_space(encode(chips, {}, {}), [0, 1])
    is_ar = lambda o: o.opcode.startswith("all-reduce")  # noqa: E731
    assert r.op_seconds(is_ar) == pytest.approx(60e-9)
    assert r.busy_s(0) == pytest.approx(50e-9)
    assert r.busy_s() == pytest.approx(60e-9)       # the mean of 50, 70
    assert r.span_s() == pytest.approx(70e-9)       # the mean of 60, 80


def test_chips_outside_the_cell_are_left_out():
    raw = encode({0: [(op("a.1"), 0, 10)], 1: [(op("a.1"), 0, 90)]}, {}, {})
    assert xtrace.reduce_space(raw, [0]).busy_s() == \
        pytest.approx(10e-9)


# ---------------------------------------------------------------------------
# a recorded trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures",
                           "trace_mnist_fedmmd_topk.json")) as f:
        fix = json.load(f)
    raw = encode({0: [tuple(e) for e in fix["device"]]},
                 {"python": [tuple(h[1:]) for h in fix["host"]]},
                 fix["metadata"])
    return fix, xtrace.reduce_space(raw, [0])


def test_recorded_busy_is_the_union_of_the_ops(recorded):
    fix, r = recorded
    covered = set()
    for _, start, dur in fix["device"]:
        covered.update(range(start // 1000, (start + dur) // 1000))
    # a microsecond grid, against the exact interval union
    assert r.busy_s() == pytest.approx(len(covered) * 1e-6, rel=2e-3)
    assert r.busy_s() < r.span_s() <= fix["window_ns"] / 1e9


def test_recorded_exclusive_times_add_up_to_busy(recorded):
    _, r = recorded
    assert sum(t for _, t in r.top_ops(10 ** 6)) == \
        pytest.approx(r.busy_s(), rel=1e-9)
    assert r.op_seconds(lambda o: True) == pytest.approx(r.busy_s(),
                                                         rel=1e-9)


def test_recorded_per_name_device_time(recorded):
    fix, r = recorded
    sort_ns = sum(d for n, _, d in fix["device"] if n.startswith("%sort."))
    gram_ns = sum(d for n, _, d in fix["device"]
                  if "gram_sum" in n.split(" = ")[0])
    assert sort_ns > 0 and gram_ns > 0
    assert r.op_seconds(lambda o: o.opcode == "sort") == \
        pytest.approx(sort_ns / 1e9)
    assert r.op_seconds(lambda o: "gram_sum" in o.tf_op) == \
        pytest.approx(gram_ns / 1e9)
    # the top ops are named by their name stack, the sort as top_k
    assert any("top_k" in name for name, _ in r.top_ops(5))


def test_recorded_idle_gaps_are_named_by_host_spans(recorded):
    _, r = recorded
    gaps = r.idle_gaps(3)
    assert gaps and all(name.startswith("host: ") for name, _ in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
