"""Fleet-scale ILI simulation: the paper's trillion-item story.

Runs a *heterogeneous* fleet — different workloads on different FLEXIBITS
cores, one FleetPlan — through the streaming engine (DESIGN.md §9):
items flow through a fixed pool of lanes in segments, halted items are
compacted out early, and per-group cycle/energy tallies are priced
through the FLEXIFLOW carbon model, including the carbon-optimal core for
each group's (lifetime, frequency) deployment point and the TPU-side
footprint of the simulation itself.

Run:  PYTHONPATH=src python examples/fleet_simulation.py [--items 512]
"""
import argparse

import numpy as np

from repro import compile_cache
from repro.fleet import REFILLS, STEPPERS, FleetGroup, FleetPlan, run_plan
from repro.launch.mesh import make_host_mesh


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=256,
                    help="items per group")
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--seg-steps", type=int, default=1024)
    ap.add_argument("--stepper", choices=STEPPERS, default=None,
                    help="segment interpreter (DESIGN.md §9.5/§9.7); "
                         "default: the engine's choice, pallas on a TPU "
                         "and branchless elsewhere")
    ap.add_argument("--packed", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run all groups in one packed multi-program "
                         "stream (DESIGN.md §9.8); --no-packed drains "
                         "groups sequentially (the A/B baseline)")
    ap.add_argument("--refill", choices=REFILLS, default="device",
                    help="stream loop (DESIGN.md §9.9): 'device' = "
                         "resident runtime (on-device retire/refill, "
                         "async sync), 'host' = PR-4 host-refill A/B "
                         "baseline")
    ap.add_argument("--adaptive", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="adaptive supersteps: pick each segment's step "
                         "bound from the observed halt cadence "
                         "(DESIGN.md §9.9)")
    args = ap.parse_args()

    # three sub-fleets: malodor classification on the 1-bit core (long
    # lifetime, low frequency), water quality on the 4-bit core, smart
    # irrigation on the 8-bit core (frequent executions favor wide cores)
    plan = FleetPlan(groups=(
        FleetGroup(workload="MC", core="SERV", n_items=args.items, seed=0),
        FleetGroup(workload="WQ", core="QERV", n_items=args.items, seed=1),
        FleetGroup(workload="SI", core="HERV", n_items=args.items, seed=2),
    ), chunk=args.chunk, seg_steps=args.seg_steps, stepper=args.stepper,
        packed=args.packed, refill=args.refill, adaptive=args.adaptive)

    mesh = make_host_mesh()
    report = run_plan(plan, mesh=mesh)

    mode = "packed" if args.packed else "sequential"
    print(f"[fleet] {report.n_items} items on mesh {dict(mesh.shape)} "
          f"({mode} runtime, {args.refill} refill"
          f"{', adaptive supersteps' if args.adaptive else ''})")
    if report.packed is not None:
        p = report.packed
        print(f"[fleet] sync: {p.host_syncs} blocking host syncs over "
              f"{p.n_segments} segments ({p.sync_wait_s * 1e3:.1f} ms "
              f"waited), refill host work {p.refill_wall_s * 1e3:.1f} ms")
    mc = report.groups[0].result
    print(f"[fleet] MC malodor score histogram: "
          f"{np.bincount(mc.out, minlength=5)}")
    print(report.format())


if __name__ == "__main__":
    main()
