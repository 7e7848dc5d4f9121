"""jit'd public wrappers for the Pallas kernels, with implementation dispatch.

Select with ``impl``: ``'auto'`` (the default) takes the pure-jnp oracle
on CPU and the compiled Pallas kernel on TPU; ``'jnp'`` and ``'pallas'``
force one; ``'pallas_interpret'`` — and only it — runs a kernel in the
Pallas interpreter, which the CPU correctness tests use.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import compress_pack, ref
from repro.kernels.decode_attn import flash_decode
from repro.kernels.fusion_conv import fusion_conv
from repro.kernels.mk_mmd import gram_sum


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "jnp"
    return impl


def mk_mmd2(x, y, widths, *, impl="auto"):
    """Multi-kernel squared MMD between feature batches x [n,d], y [m,d]."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.mk_mmd2_ref(x, y, widths)
    interpret = impl == "pallas_interpret"
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    n, m = x.shape[0], y.shape[0]
    # median-heuristic sigma from the cross sq-distances (O(nm d) but cheap
    # relative to the Gram sums; stop-grad like the oracle).
    sigma = jax.lax.stop_gradient(jnp.mean(ref.sq_dists(x, y))) + 1e-8
    sxx = gram_sum(x, x, sigma, widths, interpret=interpret)
    syy = gram_sum(y, y, sigma, widths, interpret=interpret)
    sxy = gram_sum(x, y, sigma, widths, interpret=interpret)
    return sxx / (n * n) + syy / (m * m) - 2.0 * sxy / (n * m)


def fused_fusion_conv(f_g, f_l, w, *, impl="auto"):
    """FedFusion conv operator: W . concat(f_g, f_l) along channels."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.fusion_conv_ref(f_g, f_l, w)
    return fusion_conv(f_g, f_l, w, interpret=(impl == "pallas_interpret"))


def quantize_pack(x, scale, noise, *, bits=8, impl="auto"):
    """Fused stochastic-quantize + bit-pack of a flat fp32 tensor.

    Wire format of ``repro.compress``: int8 codes, or nibble-packed uint8
    for ``bits=4``.  All impls produce bit-identical packed payloads."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.quant_pack_ref(x, scale, noise, bits=bits)
    return compress_pack.quant_pack(x, scale, noise, bits=bits,
                                    interpret=(impl == "pallas_interpret"))


def quantize_unpack(packed, scale, *, bits=8, n=None, impl="auto"):
    """Scatter-unpack quantized codes back to fp32 [n]."""
    impl = _resolve(impl)
    if impl == "jnp":
        m = packed.shape[0]
        n = (m if bits == 8 else 2 * m) if n is None else n
        return ref.quant_unpack_ref(packed, scale, bits=bits, n=n)
    return compress_pack.quant_unpack(packed, scale, bits=bits, n=n,
                                      interpret=(impl == "pallas_interpret"))


def topk_threshold_select(x, thresh, *, impl="auto"):
    """Dense top-k select: keep entries with |x| >= thresh, zero the rest."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.topk_select_ref(x, thresh)
    return compress_pack.topk_select(x, thresh,
                                     interpret=(impl == "pallas_interpret"))


def ef_gather(table, idx, *, impl="auto"):
    """Pull the sampled clients' rows [k, ...] out of a device-resident
    per-client table [N, ...] (error-feedback residuals, ``repro.engine``).

    The Pallas kernel scalar-prefetches ``idx`` (``PrefetchScalarGridSpec``)
    so the row index feeds the DMA engine directly — it compiles TPU-native
    and ``auto`` selects it there; on CPU ``auto`` stays on the jnp
    ``take`` oracle (interpret mode is for the correctness tests)."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.ef_gather_ref(table, idx)
    return compress_pack.ef_gather(table, idx,
                                   interpret=(impl == "pallas_interpret"))


def ef_scatter(table, idx, rows, *, impl="auto"):
    """Write rows [k, ...] back into table [N, ...] at the (unique) idx.

    The jnp path is ``table.at[idx].set(rows)`` — under jit with the table
    donated, XLA performs this in place; the Pallas path aliases the table
    buffer explicitly (``input_output_aliases``) and scalar-prefetches
    ``idx`` so each row writes back as one direct VMEM->HBM DMA.  Either
    way the full-federation EF tree is updated without a device->host
    round-trip.  ``auto`` -> pallas on TPU, jnp elsewhere."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.ef_scatter_ref(table, idx, rows)
    return compress_pack.ef_scatter(table, idx, rows,
                                    interpret=(impl == "pallas_interpret"))


def gqa_flash_decode(q, k_cache, v_cache, valid_len=None, *, impl="auto"):
    """One-token GQA decode attention against a KV cache."""
    impl = _resolve(impl)
    if impl == "jnp":
        vl = k_cache.shape[1] if valid_len is None else valid_len
        return ref.decode_attn_ref(q, k_cache, v_cache, vl)
    return flash_decode(q, k_cache, v_cache, valid_len,
                        interpret=(impl == "pallas_interpret"))
