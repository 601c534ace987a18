"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes
artifacts/benchmarks.json with the derived headline quantities.
"""
from __future__ import annotations

import json
import os
import sys

from benchmarks.common import Timer

BENCHES = [
    ("fig2a_instruction_mix", "benchmarks.paper_tables"),
    ("fig2b_dynamic_instructions", "benchmarks.paper_tables"),
    ("table3_memory", "benchmarks.paper_tables"),
    ("table7_fig9_ppa", "benchmarks.paper_tables"),
    ("table6_feasibility", "benchmarks.paper_tables"),
    ("table8_memory_power", "benchmarks.paper_tables"),
    ("fig11_embodied", "benchmarks.paper_tables"),
    ("fig5_selection_maps", "benchmarks.paper_tables"),
    ("fig6_pareto", "benchmarks.paper_tables"),
    ("table5_at_scale", "benchmarks.paper_tables"),
    ("fig12_sensitivity_mix", "benchmarks.paper_tables"),
    ("fig13_sensitivity_energy", "benchmarks.paper_tables"),
    ("planner_grid", "benchmarks.serving"),
    ("roofline_table", "benchmarks.rooflines"),
    ("fleet_streaming_vs_monolithic", "benchmarks.fleet"),
]


def main() -> None:
    import importlib
    derived_all = {}
    failures = []
    for fn_name, mod_name in BENCHES:
        try:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, fn_name)
            with Timer() as t:
                rows, derived = fn()
            for name, a, b in rows:
                print(f"{name},{t.us / max(len(rows), 1):.1f},{a};{b}")
            derived_all[fn_name] = derived
            print(f"{fn_name},{t.us:.1f},{json.dumps(derived, default=str)}")
        except Exception as e:  # keep the harness running
            failures.append((fn_name, f"{type(e).__name__}: {e}"))
            print(f"{fn_name},0,ERROR:{type(e).__name__}:{e}")
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/benchmarks.json", "w") as f:
        json.dump(derived_all, f, indent=1, default=str)
    if failures:
        print("FAILURES:", failures, file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
