"""Device microseconds of the DMR refill program per segment.

Under DMR the resident runtime's boundary op (`refill_dmr` in
`engine._resident_refill_runner`) compares each lane pair's digest,
rolls disagreeing pairs back to the segment's snapshot, quarantines a
pair that keeps disagreeing, and retires and refills agreeing pairs;
its device time over `PackedStats.n_segments` is the cost of one
boundary.
"""
LAYER = "DMR compare, rollback and pair refill"
UNIT = "us/segment"
SOURCE = "device_trace"
MOVES = "sim_minstr_per_s"
PROGRAMS = ("jit_refill_dmr",)


def read(trace, counters):
    t = trace.program_s(PROGRAMS)
    if t is None or not counters.get("n_segments"):
        return None
    return t * 1e6 / counters["n_segments"]
