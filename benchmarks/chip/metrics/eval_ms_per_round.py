"""Device time of evaluation per round the trace saw, the most of any
chip: the ops under the program's ``eval`` scope (the global model's
forward over the padded test set and its masked metrics)."""
import scope_time

LAYER = "round function"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(obs):
    return scope_time.ms_per_round(obs, "eval")
