from repro.roofline.analysis import (PEAKS, DevicePeaks,  # noqa: F401
                                     Roofline, analyze, device_peaks,
                                     model_flops)
from repro.roofline.hlo import (collective_bytes,  # noqa: F401
                                collective_op_counts, collective_summary,
                                entry_io_aliases, entry_param_shapes)
