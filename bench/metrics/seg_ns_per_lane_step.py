"""Device nanoseconds of the segment programs per lane-step slot.

The segment program steps every lane of the pool `seg_steps` times; its
device time over `PackedStats.lane_steps` (every slot of the pool,
busy or idle) is what one lane-step costs the chip.
"""
LAYER = "segment stepper"
UNIT = "ns/lane-step"
SOURCE = "device_trace"
MOVES = "sim_minstr_per_s"
PROGRAMS = ("jit_seg",)


def read(trace, counters):
    t = trace.program_s(PROGRAMS)
    if t is None or not counters.get("lane_steps"):
        return None
    return t * 1e9 / counters["lane_steps"]
