"""Share of lane-step slots whose work DMR rollbacks threw away.

`PackedStats.discarded` (n_instr less the snapshot's n_instr, over both
lanes of every pair rolled back) over `PackedStats.lane_steps`: the
segments that ran again because a pair disagreed. A program that does
not count `discarded` gives nothing.
"""
LAYER = "DMR rollback"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "sim_minstr_per_s"


def read(trace, counters):
    if counters.get("discarded") is None or not counters.get("lane_steps"):
        return None
    return 100.0 * counters["discarded"] / counters["lane_steps"]
