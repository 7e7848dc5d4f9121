"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's busy intervals over the window, both
on the trace's clock (the window from the chip's first op to its last),
averaged over the cell's chips."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def read(obs):
    if obs.trace is None or not obs.trace.has_device_events():
        return None
    return 100.0 * (1.0 - obs.trace.busy_s() / obs.trace.span_s())
