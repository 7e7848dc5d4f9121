"""Pallas TPU kernels for the ``repro.compress`` wire hot paths.

Three kernels back the codec subsystem (oracles in ``kernels/ref.py``):

* ``quant_pack``   — fused stochastic-quantize + bit-pack: fp32 deltas are
  scaled, stochastically rounded (the uniform offsets arrive as an input so
  the kernel stays deterministic and vmap/test friendly) and written as int8
  codes, or as two 4-bit nibbles per uint8 for ``bits=4``.  One pass over
  the tensor, no intermediate integer tensor in HBM.
* ``quant_unpack`` — scatter-unpack: codes -> fp32, nibble split for int4.
* ``topk_select``  — magnitude threshold select ``x * (|x| >= t)``: the
  dense decode∘encode of top-k sparsification, used to form the error-
  feedback residual without materialising gather/scatter indices.
* ``ef_gather`` / ``ef_scatter`` — row gather/scatter for the device-
  resident per-client error-feedback table (``repro.engine``): the full-
  federation EF tree lives flattened as [n_clients, n] and each round
  pulls/pushes only the sampled clients' rows.  The sampled client ids are
  SCALAR-PREFETCH operands (``pltpu.PrefetchScalarGridSpec``): the block
  index maps read ``cids[i]`` before the kernel body runs, so the row
  index feeds the DMA engine directly — each grid step is one HBM<->VMEM
  row copy with no in-kernel address computation, which is what lets the
  kernels compile TPU-native (the pre-prefetch version read the index
  from an ANY-memory ref inside the body and could only interpret).
  ``ef_scatter`` aliases the table input to its output
  (``input_output_aliases``) so the update is in-place — no
  [n_clients, n]-sized copy per round, which is the whole point of
  keeping EF on device.

All elementwise kernels view the flat tensor as [rows, 128] lanes and run
a 1-D grid over row blocks; wrappers pad to tile multiples and slice the
result back, so callers see exact flat shapes.  Row blocks are multiples
of 32 rows, the native tile of the int8/uint8 code arrays, and scalars
(scale, threshold) ride in SMEM.  The int4 nibble pairs (elements 2i and
2i+1) are split into an even and an odd plane by the wrapper, so the
kernels combine two aligned planes instead of shuffling lanes.  Every
kernel is bit-identical to its oracle.  Passing ``interpret`` true runs
the Pallas interpreter (the CPU correctness tests); the production CPU
path is the jnp reference (see ``kernels/ops.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

LANES = 128
TILE_ROWS = 32          # native (32, 128) tile of the int8/uint8 codes
MAX_BLOCK_ROWS = 512    # rows per grid step: 256 KiB of f32
MAX_EF_ROWS = 1024      # [rows, 128] slab of one EF row per grid step

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _round_up(a, b):
    return -(-a // b) * b


def _row_blocks(n):
    """Elements n -> (rows, block_rows) of the [rows, 128] view."""
    rows = _round_up(max(-(-n // LANES), 1), TILE_ROWS)
    block = min(rows, MAX_BLOCK_ROWS)
    return _round_up(rows, block), block


def _as_rows(flat, rows, fill):
    """[n] -> [rows, 128], tail padded with ``fill``."""
    pad = rows * LANES - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad), constant_values=fill)
    return flat.reshape(rows, LANES)


def _elementwise_call(kernel, name, arrays, scalar, out_dtypes, rows, block,
                      interpret):
    """Run ``kernel`` over [rows, 128] arrays with one SMEM scalar."""
    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // block,),
        in_specs=[spec] * len(arrays) + [_SMEM],
        out_specs=[spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), d)
                   for d in out_dtypes],
        name=name,
        interpret=interpret,
    )(*arrays, jnp.asarray(scalar, jnp.float32).reshape(1, 1))


# ---------------------------------------------------------------------------
# quantize + pack
# ---------------------------------------------------------------------------

def _quantize(x_ref, noise_ref, inv, qmax):
    q = jnp.floor(x_ref[...] * inv + noise_ref[...])
    return jnp.clip(q, -qmax, qmax).astype(jnp.int32)


def _quant_pack8_kernel(x_ref, noise_ref, inv_ref, out_ref):
    out_ref[...] = _quantize(x_ref, noise_ref, inv_ref[0, 0],
                             127).astype(jnp.int8)


def _quant_pack4_kernel(xe_ref, xo_ref, ne_ref, no_ref, inv_ref, out_ref):
    inv = inv_ref[0, 0]
    low = _quantize(xe_ref, ne_ref, inv, 7) + 8
    high = _quantize(xo_ref, no_ref, inv, 7) + 8
    out_ref[...] = (low | (high << 4)).astype(jnp.uint8)


def quant_pack(x, scale, noise, *, bits=8, interpret=False):
    """x [n] float, noise [n] in [0,1), scale scalar -> packed codes.

    int8: int8 [n].  int4: uint8 [n/2] (n must be even), element 2i in the
    low nibble — the exact wire format of ``ref.quant_pack_ref``.
    """
    if bits not in (4, 8):
        raise ValueError(f"quant_pack bits={bits!r} must be 4 or 8")
    n = x.shape[0]
    x = x.astype(jnp.float32)
    noise = noise.astype(jnp.float32)
    if bits == 8:
        rows, block = _row_blocks(n)
        kernel, arrays = _quant_pack8_kernel, (_as_rows(x, rows, 0.0),
                                               _as_rows(noise, rows, 0.5))
        out_dtype = jnp.int8
    else:
        if n % 2:
            raise ValueError("int4 pack needs an even element count, "
                             f"got {n}")
        rows, block = _row_blocks(n // 2)
        xp, np_ = x.reshape(-1, 2), noise.reshape(-1, 2)
        kernel = _quant_pack4_kernel
        arrays = (_as_rows(xp[:, 0], rows, 0.0), _as_rows(xp[:, 1], rows, 0.0),
                  _as_rows(np_[:, 0], rows, 0.5),
                  _as_rows(np_[:, 1], rows, 0.5))
        out_dtype = jnp.uint8
    # the step enters as its reciprocal, exactly as the oracle takes it
    (packed,) = _elementwise_call(kernel, f"quant_pack_int{bits}", arrays,
                                  ref.inv_scale(scale), [out_dtype], rows,
                                  block, interpret)
    return packed.reshape(-1)[:n if bits == 8 else n // 2]


# ---------------------------------------------------------------------------
# unpack
# ---------------------------------------------------------------------------

def _quant_unpack8_kernel(q_ref, scale_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[0, 0]


def _quant_unpack4_kernel(q_ref, scale_ref, low_ref, high_ref):
    scale = scale_ref[0, 0]
    q = q_ref[...].astype(jnp.int32)
    low_ref[...] = ((q & 0xF) - 8).astype(jnp.float32) * scale
    high_ref[...] = (((q >> 4) & 0xF) - 8).astype(jnp.float32) * scale


def quant_unpack(packed, scale, *, bits=8, n=None, interpret=False):
    """Packed codes -> fp32 [n] (inverse of :func:`quant_pack`)."""
    if bits not in (4, 8):
        raise ValueError(f"quant_unpack bits={bits!r} must be 4 or 8")
    m = packed.shape[0]
    n = (m if bits == 8 else 2 * m) if n is None else n
    rows, block = _row_blocks(m)
    if bits == 8:
        (out,) = _elementwise_call(
            _quant_unpack8_kernel, "quant_unpack_int8",
            (_as_rows(packed, rows, 0),), scale, [jnp.float32], rows, block,
            interpret)
        return out.reshape(-1)[:n]
    low, high = _elementwise_call(
        _quant_unpack4_kernel, "quant_unpack_int4",
        (_as_rows(packed, rows, 0x88),), scale, [jnp.float32, jnp.float32],
        rows, block, interpret)
    inter = jnp.stack([low.reshape(-1)[:m], high.reshape(-1)[:m]], axis=-1)
    return inter.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# top-k threshold select
# ---------------------------------------------------------------------------

def _topk_select_kernel(x_ref, thresh_ref, out_ref):
    x = x_ref[...]
    out_ref[...] = jnp.where(jnp.abs(x) >= thresh_ref[0, 0], x, 0.0)


def topk_select(x, thresh, *, interpret=False):
    """x [n], thresh scalar -> x masked to entries with |x| >= thresh."""
    n = x.shape[0]
    rows, block = _row_blocks(n)
    xr = _as_rows(x.astype(jnp.float32), rows, 0.0)
    (out,) = _elementwise_call(_topk_select_kernel, "topk_select", (xr,),
                               thresh, [jnp.float32], rows, block, interpret)
    return out.reshape(-1)[:n].astype(x.dtype)


# ---------------------------------------------------------------------------
# error-feedback row gather / scatter
# ---------------------------------------------------------------------------

def _ef_slabs(table):
    """[N, ...] -> ([N, R, 128] view, n, trailing shape, slab rows).

    Each client row is cut into slabs of ``tr`` x 128 — the whole row when
    it is at most ``MAX_EF_ROWS`` x 128 (a block equal to the full
    dimension needs no alignment), else the largest multiple of 8 rows
    that divides R (the row is padded to a multiple of 8 x 128 first)."""
    N = table.shape[0]
    trail = table.shape[1:]
    flat = table.reshape(N, -1)
    n = flat.shape[1]
    R = -(-n // LANES)
    if R > MAX_EF_ROWS:
        R = _round_up(R, 8)
    if R * LANES != n:
        flat = jnp.pad(flat, ((0, 0), (0, R * LANES - n)))
    tr = R
    if R > MAX_EF_ROWS:
        tr = max(t for t in range(8, MAX_EF_ROWS + 1, 8) if R % t == 0)
    return flat.reshape(N, R, LANES), n, trail, tr


def _ef_gather_kernel(idx_ref, src_ref, out_ref):
    del idx_ref    # consumed by the index maps (scalar prefetch)
    out_ref[...] = src_ref[...]


def ef_gather(table, idx, *, interpret=False):
    """table [N, ...], idx [k] int -> the idx rows as [k, ...].

    Grid over (the k sampled clients, slabs of a row) with ``idx``
    scalar-prefetched: the input index map selects table row ``idx[i]``
    for grid step i, so the DMA engine streams exactly the sampled rows
    HBM->VMEM and the body is a pure copy."""
    view, n, trail, tr = _ef_slabs(table)
    _, R, _ = view.shape
    k = idx.shape[0]
    block = (pl.Squeezed(), tr, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, R // tr),
        in_specs=[pl.BlockSpec(block, lambda i, j, idx: (idx[i], j, 0))],
        out_specs=pl.BlockSpec(block, lambda i, j, idx: (i, j, 0)),
    )
    out = pl.pallas_call(
        _ef_gather_kernel,
        grid_spec=grid_spec,
        name="ef_gather",
        out_shape=jax.ShapeDtypeStruct((k, R, LANES), view.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), view)
    return out.reshape(k, -1)[:, :n].reshape((k,) + trail)


def _ef_scatter_kernel(idx_ref, rows_ref, table_ref, out_ref):
    del idx_ref, table_ref   # idx: index maps; table: aliased, never read
    out_ref[...] = rows_ref[...]


def ef_scatter(table, idx, rows, *, interpret=False):
    """Write rows [k, ...] into table [N, ...] at idx — in place.

    The table is donated into the kernel via ``input_output_aliases`` (the
    aliased operand never enters the body — untouched N-k rows are never
    copied) and ``idx`` is scalar-prefetched: the OUTPUT index map routes
    grid step (i, j)'s slab to table row ``idx[i]``, so the writeback is
    a direct VMEM->HBM DMA.  ``idx`` must be unique (the federated
    sampler asserts this); duplicate rows would race.
    """
    view, n, trail, tr = _ef_slabs(table)
    _, R, _ = view.shape
    k = idx.shape[0]
    rflat = rows.reshape(k, -1).astype(view.dtype)
    if rflat.shape[1] != R * LANES:
        rflat = jnp.pad(rflat, ((0, 0), (0, R * LANES - rflat.shape[1])))
    block = (pl.Squeezed(), tr, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, R // tr),
        in_specs=[
            pl.BlockSpec(block, lambda i, j, idx: (i, j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(block, lambda i, j, idx: (idx[i], j, 0)),
    )
    out = pl.pallas_call(
        _ef_scatter_kernel,
        grid_spec=grid_spec,
        name="ef_scatter",
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), rflat.reshape(k, R, LANES), view)
    return out.reshape(out.shape[0], -1)[:, :n].reshape(table.shape)
