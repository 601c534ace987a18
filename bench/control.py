"""Readings that the limits of `correct` are set from.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--seconds S]

For each seed, in one process on the cell's chips: one short window of
the cell's own traffic through the program, then the cell's check twice,
once on the program's answers and once with the control's answers put
in their place. The control is the reference in the nearest precision
below the one the configuration states: for a fleet cell, whose
simulator promises exact dynamic-timing tallies, the reference with the
dynamic timing terms dropped; for a planner cell, which asks for
float32, the reference computed in bfloat16. One JSON line per seed.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench


def readings(spec: dict, seed: int, seconds: float, devices) -> dict:
    session = spec["entry"].Session(spec["config"], spec["traffic"], seed,
                                    devices)
    session.warmup()
    records = []
    deadline = time.perf_counter() + seconds
    while not records or time.perf_counter() < deadline:
        records.append(session.request(len(records)))
    session.release()
    program, _, n = session.check(records)
    control, _, _ = session.check(records, answer=session.control(records))
    return {"seed": seed, "requests": len(records), "answers_checked": n,
            "program": {k: v for k, (v, _) in program.items()},
            "control": {k: v for k, (v, _) in control.items()},
            "limits": {k: lim for k, (_, lim) in program.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    try:
        spec = bench.resolve(args.workload)
        sys.path.insert(0, bench.program_root())
        devices = bench.require_chips(spec["cell"]["chips"])
    except bench.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(spec, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
