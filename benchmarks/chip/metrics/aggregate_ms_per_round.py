"""Device time of aggregation per round the trace saw, the most of any
chip: the ops under the program's ``aggregate`` scope (the example
weights, the weighted client sums, the psum, the server's finish and
apply, the round metrics)."""
import scope_time

LAYER = "round function"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(obs):
    return scope_time.ms_per_round(obs, "aggregate")
