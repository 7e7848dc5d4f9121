"""Structural HLO analyzer: loop-aware FLOPs / bytes / collective parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo import analyze_entry, parse_computations


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_counted():
    a = jnp.zeros((128, 256))
    b = jnp.zeros((256, 64))
    text = _compiled_text(lambda x, y: x @ y, a, b)
    cost = analyze_entry(text)
    want = 2 * 128 * 256 * 64
    assert want * 0.99 <= cost.flops <= want * 1.5  # layout noise tolerated


def test_scan_multiplies_by_trip_count():
    """The whole point of the custom analyzer: a scanned body counts
    trip_count times, not once (XLA cost_analysis counts it once)."""
    w = jnp.zeros((64, 64))

    def one(x):
        return x @ w

    def scanned(x):
        def body(h, _):
            return one(h), None
        h, _ = jax.lax.scan(body, x, None, length=10)
        return h

    t1 = _compiled_text(one, jnp.zeros((8, 64)))
    t10 = _compiled_text(scanned, jnp.zeros((8, 64)))
    c1 = analyze_entry(t1)
    c10 = analyze_entry(t10)
    assert c10.flops >= 9 * c1.flops, (c1.flops, c10.flops)
    assert c10.flops <= 12 * c1.flops


def test_nested_scan_multiplies():
    w = jnp.zeros((32, 32))

    def nested(x):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=4)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h

    cost = analyze_entry(_compiled_text(nested, jnp.zeros((8, 32))))
    want = 12 * 2 * 8 * 32 * 32
    assert want * 0.9 <= cost.flops <= want * 1.6


def test_bytes_positive_for_memory_bound_op():
    x = jnp.zeros((1024, 1024))
    cost = analyze_entry(_compiled_text(lambda a: a.T + 1.0, x))
    assert cost.bytes >= 2 * 1024 * 1024 * 4  # read + write at least


def test_no_collectives_on_single_device():
    x = jnp.zeros((64, 64))
    cost = analyze_entry(_compiled_text(lambda a: a @ a, x))
    assert cost.total_coll_bytes == 0


def test_parse_finds_entry():
    text = _compiled_text(lambda a: a * 2, jnp.zeros(4))
    comps, entry = parse_computations(text)
    assert entry in comps
    assert len(comps[entry].ops) >= 1


def test_collective_parsing_from_synthetic_hlo():
    """Hand-written HLO snippet with an all-reduce: payload counted once."""
    text = """
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256] parameter(0)
  ROOT %ar = f32[128,256] all-reduce(%p0), to_apply=%add
}
"""
    cost = analyze_entry(text)
    assert cost.coll_bytes["all-reduce"] == 128 * 256 * 4
    assert cost.coll_counts["all-reduce"] == 1


def test_device_peaks_keyed_by_kind():
    """Roofline terms divide by the peaks of the device kind they are for;
    a kind with no published peaks is an error, never a default."""
    from repro.roofline import PEAKS, device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert v5e.source and set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
