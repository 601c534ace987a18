"""Share of lane-step slots that retired an instruction.

Retired instructions (`FleetReport.busy_steps`) over the pool's
lane-step slots (`PackedStats.lane_steps`): what lane admission and the
stream loop leave idle, at segment ends and in the drain tail.
"""
LAYER = "stream loop and admission"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "sim_minstr_per_s"


def read(trace, counters):
    if not counters.get("lane_steps"):
        return None
    return 100.0 * counters["busy_steps"] / counters["lane_steps"]
