"""Device time of the uplink codec per round the trace saw, the most of
any chip: the device time of the ops the trace names after it
(``top_k`` and ``sort``, the ``ef_gather`` / ``ef_scatter`` and
``quant_pack_*`` / ``quant_unpack_*`` kernels) and of every op whose
source lies in the program's ``compress`` package (the payload gather,
the decode and the residual scatters), each with what nests in it,
counted once."""
LAYER = "codecs and EF"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "rounds_per_s"

NAMES = ("top_k", "/sort", "ef_gather", "ef_scatter", "quant_pack",
         "quant_unpack")


def is_codec(op):
    return (any(n in op.tf_op or n in op.name for n in NAMES)
            or "repro/compress/" in op.source)


def read(obs):
    if obs.trace is None or obs.trace.count(is_codec) == 0:
        return None
    return 1000.0 * obs.trace.op_seconds(is_codec) / obs.traced_rounds
