"""The ``gram_sum`` kernel's share of its roofline: the least time its
calls in the traced rounds could take on the chip (FedMMD's three Gram
sums per local step and client, from their shapes by
``flops.gram_sum_cost``, against the bf16 peak and HBM bandwidth) over
the kernel's summed device time.  Its backward runs in plain XLA ops
(a custom VJP) and is not the kernel's."""
import flops

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def is_kernel(op):
    return "gram_sum" in op.tf_op or "gram_sum" in op.name


def read(obs):
    tr = obs.trace
    if tr is None or tr.count(is_kernel) == 0:
        return None
    fl = obs.traffic["fl"]
    c = obs.model["conv_channels"][-1]
    b = fl["local_batch"]
    calls = (3 * fl["clients_per_round"] // obs.chips * fl["local_steps"]
             * fl.get("local_epochs", 1) * obs.traced_rounds)
    f, nb = flops.gram_sum_cost(b, b, c, len(fl["mmd_widths"]))
    t_min, _ = flops.least_time(f * calls, nb * calls,
                                flops.peaks(obs.device_kind))
    return 100.0 * t_min / tr.op_seconds(is_kernel)
