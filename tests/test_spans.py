"""The program's own spans, recorded by the JAX profiler on the CPU.

`run_plan` and `run_sweep` open `jax.profiler.TraceAnnotation` spans at
their layer boundaries (`fleet.*` in fleet/plan.py and the engine's
`_SyncClock`, `sweep.*` in core/sweep.py). A trace puts them on the
host plane, where the benchmark reads them beside the device planes.
These tests pin what the spans count and how they nest."""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.core.sweep import LifetimeDist, SweepSpec, run_sweep
from repro.core.carbon import DeviceProfile
from repro.fleet import FleetGroup, FleetPlan, run_plan

DAY = 86_400.0


def traced(tmp_path, fn):
    """fn() under the profiler: (its result, the program's spans as
    (start_ns, end_ns, name), in start order)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("fleet.", "sweep.")):
                    s = int(e.start_ns)
                    spans.append((s, s + int(e.duration_ns), e.name))
    return out, sorted(spans)


def named(spans, name):
    return [(s, e) for s, e, n in spans if n == name]


def inside(inner, outer):
    """Every interval of `inner` lies within one interval of `outer`."""
    return all(any(so <= s and e <= eo for so, eo in outer)
               for s, e in inner)


def plan(**kw):
    return FleetPlan(groups=(
        FleetGroup(workload="WQ", core="SERV", n_items=40, seed=1),
        FleetGroup(workload="MC", core="HERV", n_items=24, seed=2),
    ), chunk=16, seg_steps=128, **kw)


def test_fleet_spans_count_the_syncs_and_nest_in_the_job(tmp_path):
    rep, spans = traced(tmp_path, lambda: run_plan(plan()))
    stats = rep.packed
    syncs = named(spans, "fleet.sync")
    assert len(syncs) == stats.host_syncs > 0
    job, stream = named(spans, "fleet.job"), named(spans, "fleet.stream")
    assert len(job) == len(stream) == 1
    assert len(named(spans, "fleet.static")) == 1
    assert len(named(spans, "fleet.report")) == 1
    assert inside(stream + named(spans, "fleet.static")
                  + named(spans, "fleet.report"), job)
    loop = [(s, e) for s, e, n in spans if n in (
        "fleet.sync", "fleet.dispatch", "fleet.restock", "fleet.upload",
        "fleet.drain")]
    assert inside(loop, stream)
    # one dispatch per segment, and the trailing one
    n_dispatch = len(named(spans, "fleet.dispatch"))
    assert n_dispatch == stats.n_segments + 1
    # the stats total the spans they are taken in
    sync_s = sum(e - s for s, e in syncs) / 1e9
    restock_s = sum(e - s for s, e in named(spans, "fleet.restock")) / 1e9
    assert stats.sync_wait_s <= sync_s + 1e-3
    assert stats.refill_wall_s <= restock_s + 1e-3
    assert stats.sync_wait_s >= 0.5 * sync_s - 1e-3
    assert len(named(spans, "fleet.drain")) == 1
    assert len(named(spans, "fleet.upload")) == n_dispatch
    assert len(named(spans, "fleet.restock")) == n_dispatch + 1


def test_fleet_checkpoint_is_a_span_of_the_stream(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    rep, spans = traced(tmp_path / "trace", lambda: run_plan(
        plan(), checkpoint_dir=ckpt, checkpoint_every=1))
    saves = named(spans, "fleet.checkpoint")
    assert saves and inside(saves, named(spans, "fleet.stream"))
    # a save reads the pool back: its syncs are counted too
    assert len(named(spans, "fleet.sync")) == rep.packed.host_syncs


def test_sweep_spans_one_step_per_tile_and_one_readback_per_whatif(
        tmp_path):
    prof = DeviceProfile(n_one_stage=600, n_two_stage=400, vm_kb=0.4,
                         nvm_kb=1.0)
    spec = SweepSpec(
        workloads=("w0", "w1"), profiles=(prof, prof),
        dists=(LifetimeDist.lognormal(DAY * 30, 1.8),
               LifetimeDist.point(DAY * 100)),
        execs_per_day=(1.0, 24.0, 96.0), intensities=(0.028, 0.367),
        volumes=(1.0, 1e9), draws=8, seed=5)
    tile = 10
    res, spans = traced(tmp_path, lambda: run_sweep(spec, tile_cells=tile))
    n_tiles = -(-res.n_cells // tile)
    assert n_tiles > 1
    whatif = named(spans, "sweep.whatif")
    assert len(whatif) == 1
    steps, reads = named(spans, "sweep.step"), named(spans, "sweep.readback")
    assert len(steps) == n_tiles
    assert len(reads) == res.host_reads == 1
    assert len(named(spans, "sweep.prepare")) == 1
    assert len(named(spans, "sweep.finish")) == 1
    assert inside(steps + reads + named(spans, "sweep.prepare")
                  + named(spans, "sweep.finish"), whatif)
    # the one read-back follows the last tile's step
    assert reads[0][0] >= max(e for _, e in steps)
