"""Pallas kernels (interpret mode) vs the pure-jnp oracles in ref.py.

Each kernel is swept over shapes and dtypes; the Pallas body executes in
Python on CPU (interpret=True) and must match the oracle to tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attn import flash_decode
from repro.kernels.fusion_conv import fusion_conv
from repro.kernels.mk_mmd import gram_sum

# ---------------------------------------------------------------------------
# MK-MMD gram-sum + mmd2
# ---------------------------------------------------------------------------

WIDTHS = (1.0, 2.0, 4.0, 8.0, 16.0)


@pytest.mark.parametrize("n,m,d", [
    (8, 8, 4), (16, 8, 32), (100, 64, 16),      # non-aligned n
    (130, 130, 8),                               # > 1 tile (tile=128)
    (256, 200, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_sum_matches_ref(n, m, d, dtype):
    kx, ky = jax.random.split(jax.random.PRNGKey(n + m))
    x = jax.random.normal(kx, (n, d), dtype)
    y = jax.random.normal(ky, (m, d), dtype)
    sigma = 3.7
    got = gram_sum(x, y, sigma, WIDTHS, interpret=True)
    want = ref.gram_sum_ref(x, y, sigma, WIDTHS)
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype == jnp.bfloat16
                               else 1e-5)


@pytest.mark.parametrize("n,m,d", [(16, 16, 8), (64, 32, 32), (130, 70, 16)])
def test_mk_mmd2_grad_pallas_matches_jnp(n, m, d):
    """Reverse mode through the kernel (its custom VJP) == autodiff of the
    oracle, for both feature batches."""
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (n, d))
    y = 0.5 * jax.random.normal(ky, (m, d)) + 1.0
    grad = lambda impl: jax.grad(  # noqa: E731
        lambda a, b: ops.mk_mmd2(a, b, WIDTHS, impl=impl), (0, 1))(x, y)
    for got, want in zip(grad("pallas_interpret"), grad("jnp")):
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize("n,m,d", [(16, 16, 8), (64, 32, 32), (130, 70, 16)])
def test_mk_mmd2_pallas_matches_jnp(n, m, d):
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, d))
    y = 0.5 * jax.random.normal(ky, (m, d)) + 1.0
    got = ops.mk_mmd2(x, y, WIDTHS, impl="pallas_interpret")
    want = ops.mk_mmd2(x, y, WIDTHS, impl="jnp")
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_mmd_zero_for_identical():
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    v = float(ref.mk_mmd2_ref(x, x, WIDTHS))
    assert abs(v) < 1e-5


def test_mmd_positive_for_shifted():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    y = x + 3.0
    assert float(ref.mk_mmd2_ref(x, y, WIDTHS)) > 0.01


def test_mmd_symmetric():
    kx, ky = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (32, 8))
    y = jax.random.normal(ky, (24, 8)) * 2.0
    a = float(ref.mk_mmd2_ref(x, y, WIDTHS))
    # mk_mmd2 uses sigma from the cross-distances, symmetric in (x, y)
    b = float(ref.mk_mmd2_ref(y, x, WIDTHS))
    np.testing.assert_allclose(a, b, atol=1e-5)  # f32 summation-order noise


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 64), d=st.sampled_from([2, 8, 33]),
       scale=st.floats(0.1, 4.0), shift=st.floats(-2.0, 2.0))
def test_mmd_property_nonneg_and_grows_with_shift(n, d, scale, shift):
    """Biased-estimator MMD^2 >= 0, and distribution shift increases it."""
    x = jax.random.normal(jax.random.PRNGKey(n * d), (n, d)) * scale
    same = float(ref.mk_mmd2_ref(x, x, WIDTHS))
    far = float(ref.mk_mmd2_ref(x, x + shift, WIDTHS))
    assert same >= -1e-6
    assert far >= same - 1e-6


def test_mmd_gradient_flows():
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 8))
    y = x + 1.0
    g = jax.grad(lambda a: ref.mk_mmd2_ref(a, y, WIDTHS))(x)
    assert float(jnp.abs(g).max()) > 0
    assert bool(jnp.all(jnp.isfinite(g)))


def test_mmd_permutation_invariant():
    """MMD is a set statistic: shuffling examples must not change it."""
    x = jax.random.normal(jax.random.PRNGKey(4), (20, 6))
    y = jax.random.normal(jax.random.PRNGKey(5), (20, 6)) + 0.5
    a = float(ref.mk_mmd2_ref(x, y, WIDTHS))
    b = float(ref.mk_mmd2_ref(x[::-1], y, WIDTHS))
    np.testing.assert_allclose(a, b, atol=1e-5)  # f32 summation-order noise


# ---------------------------------------------------------------------------
# FedFusion conv kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,C", [
    ((4, 16), 16),            # [B, C]
    ((2, 7, 32), 32),         # [B, S, C] non-aligned token count
    ((2, 5, 5, 64), 64),      # [B, H, W, C] CNN feature maps
    ((300, 128), 128),        # token axis > tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fusion_conv_matches_ref(shape, C, dtype):
    ks = jax.random.split(jax.random.PRNGKey(shape[0] * C), 3)
    fg = jax.random.normal(ks[0], shape, dtype)
    fl = jax.random.normal(ks[1], shape, dtype)
    w = jax.random.normal(ks[2], (2 * C, C), dtype) / np.sqrt(2 * C)
    got = fusion_conv(fg, fl, w, interpret=True)
    want = ref.fusion_conv_ref(fg, fl, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("shape,C", [((4, 16), 16), ((2, 5, 5, 64), 64),
                                     ((300, 128), 128)])
def test_fusion_conv_grad_matches_ref(shape, C):
    """Reverse mode through the kernel (its custom VJP) == autodiff of the
    oracle, for both feature streams and the operator weight."""
    ks = jax.random.split(jax.random.PRNGKey(C), 3)
    fg = jax.random.normal(ks[0], shape)
    fl = jax.random.normal(ks[1], shape)
    w = jax.random.normal(ks[2], (2 * C, C)) / np.sqrt(2 * C)
    loss = lambda impl: lambda a, b, c: jnp.sum(  # noqa: E731
        ops.fused_fusion_conv(a, b, c, impl=impl) ** 2)
    got = jax.grad(loss("pallas_interpret"), (0, 1, 2))(fg, fl, w)
    want = jax.grad(loss("jnp"), (0, 1, 2))(fg, fl, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


def test_fusion_conv_equals_concat_matmul():
    """The kernel's split-W form == literal concat @ W (paper Eq. 6)."""
    C = 24
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    fg = jax.random.normal(ks[0], (10, C))
    fl = jax.random.normal(ks[1], (10, C))
    w = jax.random.normal(ks[2], (2 * C, C))
    want = jnp.concatenate([fg, fl], axis=-1) @ w
    got = ref.fusion_conv_ref(fg, fl, w)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(1, 80), c=st.sampled_from([4, 16, 40]))
def test_fusion_conv_property_sweep(t, c):
    ks = jax.random.split(jax.random.PRNGKey(t * c), 3)
    fg = jax.random.normal(ks[0], (t, c))
    fl = jax.random.normal(ks[1], (t, c))
    w = jax.random.normal(ks[2], (2 * c, c))
    got = fusion_conv(fg, fl, w, tile_t=16, interpret=True)
    want = ref.fusion_conv_ref(fg, fl, w)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# GQA flash-decode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,H,KV,hd,valid", [
    (1, 64, 4, 2, 16, 64),
    (2, 128, 8, 1, 32, 100),     # MQA + partial validity
    (2, 100, 4, 4, 16, 77),      # MHA + non-aligned L
    (1, 1024, 8, 2, 64, 1024),   # multi-block cache
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(B, L, H, KV, hd, valid, dtype):
    ks = jax.random.split(jax.random.PRNGKey(L + valid), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, L, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, L, KV, hd), dtype)
    got = flash_decode(q, k, v, valid, block_l=64, interpret=True)
    want = ref.decode_attn_ref(q, k, v, valid)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(L=st.integers(8, 200), valid=st.integers(1, 200),
       KV=st.sampled_from([1, 2, 4]), rep=st.sampled_from([1, 2, 3]))
def test_flash_decode_property_sweep(L, valid, KV, rep):
    valid = min(valid, L)
    H, hd = KV * rep, 8
    ks = jax.random.split(jax.random.PRNGKey(L * valid), 3)
    q = jax.random.normal(ks[0], (1, 1, H, hd))
    k = jax.random.normal(ks[1], (1, L, KV, hd))
    v = jax.random.normal(ks[2], (1, L, KV, hd))
    got = flash_decode(q, k, v, valid, block_l=32, interpret=True)
    want = ref.decode_attn_ref(q, k, v, valid)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_flash_decode_ignores_invalid_tail():
    """Garbage beyond valid_len must not affect the output."""
    B, L, H, KV, hd, valid = 1, 64, 2, 1, 8, 40
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, L, KV, hd))
    v = jax.random.normal(ks[2], (B, L, KV, hd))
    k_junk = k.at[:, valid:].set(1e4)
    v_junk = v.at[:, valid:].set(-1e4)
    a = flash_decode(q, k, v, valid, block_l=16, interpret=True)
    b = flash_decode(q, k_junk, v_junk, valid, block_l=16, interpret=True)
    np.testing.assert_allclose(a, b, atol=1e-6)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------

def test_ops_auto_resolves_to_jnp_on_cpu():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    got = ops.mk_mmd2(x, x + 1.0, WIDTHS, impl="auto")
    want = ops.mk_mmd2(x, x + 1.0, WIDTHS, impl="jnp")
    np.testing.assert_allclose(got, want)


def test_gqa_flash_decode_wrapper_paths_agree():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16))
    k = jax.random.normal(ks[1], (2, 96, 2, 16))
    v = jax.random.normal(ks[2], (2, 96, 2, 16))
    a = ops.gqa_flash_decode(q, k, v, 80, impl="jnp")
    b = ops.gqa_flash_decode(q, k, v, 80, impl="pallas_interpret")
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# EF gather / scatter (repro.engine's device-resident error-feedback table)
# ---------------------------------------------------------------------------

# the last case is longer than one VMEM slab of a row (MAX_EF_ROWS x 128):
# the row is cut into slabs and the grid runs over (client, slab)
_LONG_ROW = 1030 * 128 + 3


@pytest.mark.parametrize("N,n,k", [(6, 256, 3), (5, 100, 5), (16, 384, 4),
                                   (3, 7, 2), (4, _LONG_ROW, 2)])
def test_ef_gather_matches_ref(N, n, k):
    key = jax.random.PRNGKey(N * n)
    table = jax.random.normal(key, (N, n))
    idx = jax.random.permutation(key, N)[:k].astype(jnp.int32)
    want = ops.ef_gather(table, idx, impl="jnp")
    got = ops.ef_gather(table, idx, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("N,n,k", [(6, 256, 3), (5, 100, 5), (16, 384, 4),
                                   (4, _LONG_ROW, 2)])
def test_ef_scatter_matches_ref(N, n, k):
    ks = jax.random.split(jax.random.PRNGKey(N + n), 3)
    table = jax.random.normal(ks[0], (N, n))
    idx = jax.random.permutation(ks[1], N)[:k].astype(jnp.int32)
    rows = jax.random.normal(ks[2], (k, n))
    want = ops.ef_scatter(table, idx, rows, impl="jnp")
    got = ops.ef_scatter(table, idx, rows, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # untouched rows preserved, selected rows replaced
    np.testing.assert_array_equal(np.asarray(want[np.asarray(idx)]),
                                  np.asarray(rows))


def test_ef_scatter_gather_roundtrip_multidim():
    """Trailing dims beyond 2-D flatten transparently in the wrappers."""
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    table = jax.random.normal(ks[0], (4, 3, 10))
    rows = jax.random.normal(ks[1], (2, 3, 10))
    idx = jnp.array([2, 0], jnp.int32)
    out = ops.ef_scatter(table, idx, rows, impl="pallas_interpret")
    back = ops.ef_gather(out, idx, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(back), np.asarray(rows))


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_ef_scatter_scratch_row_duplicates(impl):
    """The sharded EF exchange routes not-owned rows to a scratch row
    appended past the table (``repro.engine.superstep.ef_scatter_exchange``):
    duplicate writes may only ever land there, owned rows stay exact and
    the scratch row is discarded.  Pin that contract for both impls."""
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    table = jax.random.normal(ks[0], (5, 40))
    scratch = jnp.concatenate([table, jnp.zeros((1, 40))], axis=0)
    rows = jax.random.normal(ks[1], (4, 40))
    # rows 0 and 2 owned (table rows 3, 1); rows 1, 3 -> scratch row 5
    safe_idx = jnp.array([3, 5, 1, 5], jnp.int32)
    out = ops.ef_scatter(scratch, safe_idx, rows, impl=impl)[:5]
    want = table.at[jnp.array([3, 1])].set(rows[jnp.array([0, 2])])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
