"""Device time of local training per round the trace saw, the most of
any chip: the ops under the program's ``local_train`` scope (each
client's forward, backward, the FedMMD / FedFusion term with its kernels,
and the optimizer step)."""
import scope_time

LAYER = "round function"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(obs):
    return scope_time.ms_per_round(obs, "local_train")
