"""Pluggable uplink/downlink compression for federated communication.

The paper's headline metric is communication cost; this package supplies
the codecs that actually reduce it.  A :class:`Codec` turns a model pytree
(weights or deltas) into a wire payload and reports true wire bytes, so
``CommLog`` can account real MB instead of idealized fp32 sizes.

Codecs (select via ``FLConfig.uplink_codec`` / ``downlink_codec``):

    identity            raw fp32 (baseline)
    int8 / int4 / quant stochastic uniform quantization, per-leaf scale
                        (``quant`` reads ``FLConfig.quant_bits``)
    topk / topk_noef    top-k sparsification (+ client error feedback)
    mask / lowrank      seed-expanded random sketching

The quant hot paths (fused quantize+pack, scatter-unpack) run as Pallas
kernels on TPU with pure-jnp references on CPU — see
``repro.kernels.compress_pack`` and ``repro.kernels.ops``.  The round
calls ``Codec.roundtrip``; the topk codec answers it with an exact,
sort-free selection of the same k entries (``repro.compress.topk``).
"""
from repro.compress.codec import Codec, IdentityCodec  # noqa: F401
from repro.compress.quant import QuantCodec  # noqa: F401
from repro.compress.sketch import SketchCodec  # noqa: F401
from repro.compress.topk import TopKCodec  # noqa: F401

CODEC_NAMES = ("identity", "quant", "int8", "int4", "topk", "topk_noef",
               "mask", "lowrank")


def make_codec(name: str, *, topk_frac: float = 0.05, quant_bits: int = 8,
               impl: str = "auto") -> Codec:
    """Build a codec by config name (see :data:`CODEC_NAMES`).

    Out-of-range parameters are rejected HERE, not just in
    ``FLConfig.__post_init__`` — codecs built outside a config (tests,
    benchmarks, plugins) get the same construction-time errors.
    """
    if name in ("topk", "topk_noef", "mask", "lowrank"):
        if not 0.0 < topk_frac <= 1.0:
            raise ValueError(
                f"codec {name!r}: topk_frac={topk_frac!r} must be in (0, 1]")
    if name == "quant" and quant_bits not in (4, 8):
        raise ValueError(
            f"codec 'quant': quant_bits={quant_bits!r} must be 4 or 8")
    if name == "identity":
        return IdentityCodec()
    if name == "quant":
        return QuantCodec(quant_bits, impl=impl)
    if name in ("int8", "int4"):
        return QuantCodec(int(name[3:]), impl=impl)
    if name == "topk":
        return TopKCodec(topk_frac, error_feedback=True, impl=impl)
    if name == "topk_noef":
        return TopKCodec(topk_frac, error_feedback=False, impl=impl)
    if name == "mask":
        return SketchCodec(topk_frac, mode="mask", impl=impl)
    if name == "lowrank":
        return SketchCodec(topk_frac, mode="lowrank", impl=impl)
    raise ValueError(f"unknown codec {name!r}; choose from {CODEC_NAMES}")
