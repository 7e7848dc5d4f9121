"""Device time of the codecs and error feedback per round the trace saw,
the most of any chip: the ops under the program's ``codec`` scope
(downlink and uplink encode / decode, top-k, quantisation, the EF row
gather and scatter).  Ops the compiler makes anew without a name stack
(a rewritten scatter, layout copies) are not counted."""
import scope_time

LAYER = "codecs and EF"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(obs):
    return scope_time.ms_per_round(obs, "codec")
