"""Device microseconds of the refill programs per segment.

After each segment the resident runtime retires halted lanes and swaps
in staged items on the device; its device time over
`PackedStats.n_segments` is the cost of one swap.
"""
LAYER = "refill swap"
UNIT = "us/segment"
SOURCE = "device_trace"
MOVES = "sim_minstr_per_s"
PROGRAMS = ("jit_refill",)


def read(trace, counters):
    t = trace.program_s(PROGRAMS)
    if t is None or not counters.get("n_segments"):
        return None
    return t * 1e6 / counters["n_segments"]
