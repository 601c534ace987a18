"""Device nanoseconds of the sweep-step program per priced scenario.

The sweep step draws the lifetimes of one tile of cells, prices every
candidate core and reduces the tile (`core/sweep.py` `_sweep_step` with
`kernels/carbon_sweep.py`).
"""
LAYER = "sweep step and tile kernel"
UNIT = "ns/scenario"
SOURCE = "device_trace"
MOVES = "scenarios_per_s"
PROGRAMS = ("jit_step",)


def read(trace, counters):
    t = trace.program_s(PROGRAMS)
    if t is None or not counters.get("scenarios"):
        return None
    return t * 1e9 / counters["scenarios"]
