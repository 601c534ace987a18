"""Shared set-up of the benchmark's CPU tests: the harness loaded from its
file, and cells cut to a size a CPU test can hold."""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")


def load_harness():
    """bench/run.py as the module `bench_run` (the name `run` is too
    common to import by)."""
    if "bench_run" in sys.modules:
        return sys.modules["bench_run"]
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = mod
    spec.loader.exec_module(mod)
    return mod


def tiny(spec: dict) -> dict:
    """The cell's own traffic at a size for the CPU: a few items per
    group, or a what-if of 2 x 2 x 1 x 1 x 11 x 2 cells of 16 draws."""
    t = spec["traffic"]
    if t["entry"] == "fleet":
        t.update(items_per_group=6, check_per_group=2)
    else:
        t.update(dists=t["dists"][2:], execs_per_day=t["execs_per_day"][:2],
                 intensities=t["intensities"][2:3], volumes=t["volumes"][:1],
                 timing=["base", "wcet"], draws=16)
    return spec
