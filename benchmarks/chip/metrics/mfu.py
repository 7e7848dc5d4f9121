"""Model FLOP utilization of the whole round: the operations one round
needs by the model's shapes (``flops.model_flops_per_round``: every local
example's forward and backward, the frozen global stream, the fusion
operator, the eval forward) times the traced window's rounds per second,
over the chips' bf16 peak.  The program keeps float32 parameters at the
TPU's default matmul precision; the peak is the bf16 one."""
import flops

LAYER = "round function"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "rounds_per_s"


def read(obs):
    per_round = flops.model_flops_per_round(obs.model, obs.traffic)
    peak = flops.peaks(obs.device_kind)["bf16_flops"]
    return 100.0 * per_round * obs.rounds_per_s / (obs.chips * peak)
