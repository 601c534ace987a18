"""Share of the traced window in which no program ran on the chip, mean
over the cell's chips."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "scenarios_per_s"


def read(trace, counters):
    if not trace.busy_s or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_mean_s / trace.window_s)
