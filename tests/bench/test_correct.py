"""What `correct` rests on, on the CPU at a tiny size: the control (the
reference one precision below the configuration's) fails the check, and
so does a run whose timed path is broken underneath, for each fault the
cell can have.

The fleet entry's shard-local stream exchanges nothing between chips,
so there is no exchange to leave out."""
import dataclasses

import jax
import numpy as np
import pytest

from bench_helpers import load_harness, tiny

bench_run = load_harness()
SEED = 2**33 + 11


def _run(cell):
    spec = tiny(bench_run.resolve(cell))
    return bench_run.run(spec, seed=SEED, seconds=0.1, trace=False,
                         devices=jax.devices()[:1])


@pytest.mark.parametrize("cell", ["fs-fleet", "table2-planner"])
def test_the_control_is_not_correct(cell):
    spec = tiny(bench_run.resolve(cell))
    entry = spec["entry"]
    session = entry.Session(spec["config"], spec["traffic"], SEED,
                            jax.devices()[:1])
    records = [session.request(0)]
    sound, _, _ = session.check(records)
    control, _, _ = session.check(records, answer=session.control(records))
    assert all(v <= lim for v, lim in sound.values()), sound
    assert any(v > lim for v, lim in control.values()), control


# ---- faults planted under the fleet entry's timed path: run_packed's
# per-group results, as the engine hands them to the report
def _fleet_fault(kind):
    def alter(r):
        if kind == "state_unchanged":
            zero = np.zeros_like(r.n_instr)
            return dataclasses.replace(
                r, n_instr=zero, n_two_stage=zero.copy(),
                n_cycles=np.zeros_like(r.n_cycles),
                halted=np.zeros_like(r.halted), out=np.zeros_like(r.out),
                mix=np.zeros_like(r.mix))
        if kind == "half_left_out":
            h = r.n_items // 2
            return dataclasses.replace(
                r, n_items=h, n_instr=r.n_instr[:h],
                n_two_stage=r.n_two_stage[:h], halted=r.halted[:h],
                out=r.out[:h], n_cycles=r.n_cycles[:h])
        return dataclasses.replace(r, out=r.out + 1)         # answer_altered
    return alter


# ---- and under the planner entry's: the sweep tile kernel
def _planner_fault(kind, tile):
    def broken(emb, kwh, inten, freq, life_days, valid, cell_idx, acc, **kw):
        if kind == "half_left_out":
            valid = valid & (cell_idx % 2 == 0)
        out, new = tile(emb, kwh, inten, freq, life_days, valid, cell_idx,
                        acc, **kw)
        if kind == "state_unchanged":
            return out, acc
        if kind == "answer_altered":
            out = out._replace(sum_best=out.sum_best * 2.0)
        return out, new
    return broken


FAULTS = ["state_unchanged", "half_left_out", "answer_altered"]


@pytest.mark.parametrize("kind", FAULTS)
def test_a_broken_fleet_is_not_correct(monkeypatch, kind):
    from repro.fleet import engine
    real = engine.run_packed
    alter = _fleet_fault(kind)

    def broken(*a, **kw):
        results, stats = real(*a, **kw)
        return [alter(r) for r in results], stats
    monkeypatch.setattr(engine, "run_packed", broken)
    res = _run("fs-fleet")
    assert res["correct"] is False, res["checks"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("kind", FAULTS)
def test_a_broken_planner_is_not_correct(monkeypatch, kind):
    from repro.core import sweep
    from repro.kernels import carbon_sweep
    monkeypatch.setattr(carbon_sweep, "sweep_tile",
                        _planner_fault(kind, carbon_sweep.sweep_tile))
    sweep._sweep_step.cache_clear()
    try:
        res = _run("table2-planner")
    finally:
        sweep._sweep_step.cache_clear()
    assert res["correct"] is False, res["checks"]
    assert res["failed"] >= 1
