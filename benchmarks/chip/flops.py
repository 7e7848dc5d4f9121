"""Operations and bytes from shapes, and the chips' peaks.

Counts are multiply-adds times two of the convolutions and matrix
products; biases, ReLUs, pooling and losses are left out.  A training
example costs its forward and its backward, reckoned as twice the
forward; recomputation is not counted.
"""
from __future__ import annotations

from reference import feature_hw

# Published peaks per chip, keyed by JAX's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s.  A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def extract_flops(m):
    """One example's convolutions (SAME padding, stride 1)."""
    h, w, cin = m["input_shape"]
    k = m["conv_kernel"]
    total = 0
    for cout in m["conv_channels"]:
        total += 2 * h * w * k * k * cin * cout
        cin = cout
        h = (h - m["pool_size"]) // m["pool_stride"] + 1
        w = (w - m["pool_size"]) // m["pool_stride"] + 1
    return total


def head_flops(m):
    h, w = feature_hw(m)
    d = h * w * m["conv_channels"][-1]
    total = 0
    for units in m["fc_units"]:
        total += 2 * d * units
        d = units
    return total + 2 * d * m["n_classes"]


def model_flops_per_round(m, traffic):
    """The model operations one round needs: every local example's
    training step, the frozen global stream's forward where the algorithm
    has one, and the eval forward over the test set."""
    fl = traffic["fl"]
    algo = fl["algorithm"]
    examples = (fl["clients_per_round"] * fl["local_steps"]
                * fl.get("local_epochs", 1) * fl["local_batch"])
    fwd = extract_flops(m) + head_flops(m)
    train = 3 * fwd
    if algo == "fedmmd":
        train += extract_flops(m)
    elif algo != "fedavg":
        raise ValueError(f"no operation count for algorithm {algo!r}")
    n_eval = min(traffic["federation"]["n_test"], traffic["eval_examples"])
    per_eval = n_eval / traffic["eval_every"]
    return examples * train + per_eval * fwd


# --- kernels --------------------------------------------------------------

def gram_sum_cost(n, m, d, n_widths):
    """One ``gram_sum`` call over x [n, d], y [m, d]: the squared
    distances (2nmd), then per pair one scale and exp per width and the
    sum (counted as 3 per width); reads x, y and writes one scalar."""
    flops = 2 * n * m * d + 3 * n * m * n_widths
    nbytes = 4 * (n * d + m * d + 1)
    return flops, nbytes


def least_time(flops, nbytes, peak):
    """The roofline's least time and the bound that sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
