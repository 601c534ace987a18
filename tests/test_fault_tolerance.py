"""Fault-tolerance integration tests: checkpoint/restore exactness,
simulated-preemption resume, elastic re-mesh, data determinism, straggler
watchdog, gradient compression, and the resident fleet stream's
checkpointable state (DESIGN.md §9.12): kill-and-resume bit-exactness,
including resume onto a differently-shaped mesh."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.data.pipeline import DataConfig, host_batch
from repro.distributed import checkpoint as ckpt
from repro.distributed.compression import (compressed_allreduce,
                                           init_residuals)
from repro.launch.train import StragglerWatchdog, train_loop
from repro.models.model import build_model


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones(5, jnp.int32), "d": jnp.zeros(())}}
    ckpt.save(str(tmp_path), 3, tree)
    restored, step = ckpt.restore(str(tmp_path), tree)
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_n(tmp_path):
    tree = {"x": jnp.zeros(2)}
    for s in range(1, 6):
        ckpt.save(str(tmp_path), s, tree, keep=2)
    assert sorted(ckpt.all_steps(str(tmp_path))) == [4, 5]


def _flip_byte(path, offset):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def test_checkpoint_crc_detects_bit_flip(tmp_path):
    """npz members are STORED (uncompressed): a flipped payload byte
    loads cleanly and only the per-leaf CRC32 catches it — the error
    names both the file and the damaged leaf (DESIGN.md §9.14)."""
    tree = {"a": jnp.arange(1024, dtype=jnp.float32)}
    ckpt.save(str(tmp_path), 3, tree)
    npz = str(tmp_path / "step_3" / "arrays.npz")
    _flip_byte(npz, 300)    # inside the first member's array payload
    with pytest.raises(ckpt.CheckpointCorrupt) as ei:
        ckpt.restore(str(tmp_path), tree, step=3)
    assert ei.value.leaf == "a"
    assert "arrays.npz" in str(ei.value)


def test_checkpoint_truncation_detected(tmp_path):
    tree = {"a": jnp.arange(256, dtype=jnp.int32)}
    ckpt.save(str(tmp_path), 1, tree)
    npz = str(tmp_path / "step_1" / "arrays.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 2)
    with pytest.raises(ckpt.CheckpointCorrupt, match="arrays.npz"):
        ckpt.restore(str(tmp_path), tree, step=1)


def test_auto_resume_falls_back_to_newest_intact(tmp_path):
    """step=None restores the newest checkpoint that verifies; only
    when every step is damaged does the corruption surface."""
    tree1 = {"x": jnp.full(64, 1, jnp.int32)}
    tree2 = {"x": jnp.full(64, 2, jnp.int32)}
    ckpt.save(str(tmp_path), 1, tree1)
    ckpt.save(str(tmp_path), 2, tree2)
    npz2 = str(tmp_path / "step_2" / "arrays.npz")
    with open(npz2, "r+b") as f:
        f.truncate(10)
    restored, step = ckpt.restore(str(tmp_path), tree1)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["x"]),
                                  np.asarray(tree1["x"]))
    npz1 = str(tmp_path / "step_1" / "arrays.npz")
    _flip_byte(npz1, 250)
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.restore(str(tmp_path), tree1)


@pytest.mark.slow
def test_preemption_resume_exact(tmp_path):
    """Train 6 steps straight vs 3 steps -> 'preempt' -> resume 3 more;
    final losses must match exactly (deterministic data + donated state)."""
    cfg = get_smoke_config("qwen2-1.5b")
    d1 = str(tmp_path / "a")
    d2 = str(tmp_path / "b")
    full = train_loop(cfg=cfg, steps=6, batch=4, seq=32, ckpt_dir=d1,
                      ckpt_every=3, log=lambda *a: None)
    train_loop(cfg=cfg, steps=3, batch=4, seq=32, ckpt_dir=d2,
               ckpt_every=3, log=lambda *a: None)
    resumed = train_loop(cfg=cfg, steps=6, batch=4, seq=32, ckpt_dir=d2,
                         ckpt_every=3, log=lambda *a: None)
    np.testing.assert_allclose(full["losses"][3:], resumed["losses"],
                               rtol=1e-5)


def test_elastic_restart_different_mesh(tmp_path):
    """Checkpoint from mesh A restores onto a differently-shaped mesh."""
    from repro.distributed.elastic import resume_elastic
    from repro.launch.steps import make_train_step
    cfg = get_smoke_config("minitron-8b")
    model = build_model(cfg)
    opt_init, _ = make_train_step(model)
    params = model.init_params(jax.random.key(0))
    opt = opt_init(params)
    ckpt.save(str(tmp_path), 7, {"params": params, "opt": opt})

    mesh_b = jax.make_mesh((1, 1), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
    p2, o2, step = resume_elastic(str(tmp_path), model, opt_init, mesh_b)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_data_determinism_across_topologies():
    cfg = DataConfig(vocab=101, seq_len=16, global_batch=8)
    whole = host_batch(cfg, step=5, host_id=0, n_hosts=1)
    parts = [host_batch(cfg, step=5, host_id=h, n_hosts=4)
             for h in range(4)]
    glued = np.concatenate([p["tokens"] for p in parts])
    np.testing.assert_array_equal(whole["tokens"], glued)
    # and distinct across steps
    other = host_batch(cfg, step=6)
    assert not np.array_equal(whole["tokens"], other["tokens"])


def test_straggler_watchdog():
    w = StragglerWatchdog(factor=2.0, warmup=3)
    for i in range(5):
        assert not w.observe(i, 1.0)
    assert w.observe(5, 3.5)
    assert w.flagged == [(5, 3.5)]


_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_FLEET_STATE_FIELDS = ("n_instr", "n_two_stage", "halted", "out", "mix",
                       "mems", "regs", "pc", "mix_items")


def _fleet_groups():
    from benchmarks.fleet import skew_fleet, skew_program
    from repro.fleet import engine
    prog = skew_program()
    mems_a = skew_fleet(prog, 40, short_iters=8, long_iters=400,
                        long_frac=0.2, seed=13)
    mems_b = skew_fleet(prog, 24, short_iters=16, long_iters=300,
                        long_frac=0.3, seed=14)
    return [
        engine.PackedGroup(code=prog.code,
                           source=engine.array_source(mems_a),
                           n_items=40, max_steps=100_000, mem_words=32,
                           out_addr=1),
        engine.PackedGroup(code=prog.code,
                           source=engine.array_source(mems_b),
                           n_items=24, max_steps=100_000, mem_words=32,
                           out_addr=1),
    ]


def test_resident_stream_kill_and_resume_bit_exact(tmp_path):
    """Kill the resident stream mid-flight (InjectedFault at a segment
    boundary) and rerun against the same checkpoint dir: the stream
    auto-resumes from its last snapshot, drains bit-exactly equal to an
    uninterrupted run (full state + per-group mix), and the resumed
    run's total segment count matches — deterministic re-execution from
    the checkpoint, not approximate recovery (DESIGN.md §9.12)."""
    from repro.fleet import engine
    kw = dict(chunk=16, seg_steps=64, keep_state=True)
    ref, ref_stats = engine.run_packed(_fleet_groups(), **kw)
    cdir = str(tmp_path / "fleet-ck")
    with pytest.raises(engine.InjectedFault):
        engine.run_packed(_fleet_groups(), checkpoint_dir=cdir,
                          checkpoint_every=4, _crash_after_segments=10,
                          **kw)
    crashed_at = ckpt.latest_step(cdir)
    assert crashed_at is not None and crashed_at <= 10
    res, stats = engine.run_packed(_fleet_groups(), checkpoint_dir=cdir,
                                   checkpoint_every=4, **kw)
    assert stats.n_segments == ref_stats.n_segments
    for a, b in zip(ref, res):
        for f in _FLEET_STATE_FIELDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f)


def test_resident_stream_resume_skips_corrupt_newest(tmp_path):
    """Kill the stream, then damage its newest on-disk snapshot (bit
    flip) — auto-resume must fall back to the next-older intact
    checkpoint and still drain bit-exactly equal to an uninterrupted
    run (§9.14: one torn write never strands the stream)."""
    from repro.fleet import engine
    kw = dict(chunk=16, seg_steps=64, keep_state=True)
    ref, ref_stats = engine.run_packed(_fleet_groups(), **kw)
    cdir = str(tmp_path / "fleet-ck")
    with pytest.raises(engine.InjectedFault):
        engine.run_packed(_fleet_groups(), checkpoint_dir=cdir,
                          checkpoint_every=3, _crash_after_segments=10,
                          **kw)
    steps = sorted(ckpt.all_steps(cdir))
    assert len(steps) >= 2      # need an older one to fall back to
    newest = steps[-1]
    _flip_byte(os.path.join(cdir, f"step_{newest}", "arrays.npz"), 400)
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.verify(cdir, newest)
    res, stats = engine.run_packed(_fleet_groups(), checkpoint_dir=cdir,
                                   checkpoint_every=3, **kw)
    assert stats.n_segments == ref_stats.n_segments
    for a, b in zip(ref, res):
        for f in _FLEET_STATE_FIELDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f)


def test_resident_checkpoint_requires_packed_plan(tmp_path):
    from repro.fleet.plan import FleetGroup, FleetPlan, run_plan
    plan = FleetPlan(groups=(FleetGroup(workload="WQ", n_items=4),),
                     packed=False)
    with pytest.raises(ValueError, match="packed"):
        run_plan(plan, checkpoint_dir=str(tmp_path))


@pytest.mark.slow
def test_resident_stream_elastic_resume_across_mesh_shapes(tmp_path):
    """The resident checkpoint is mesh-independent: crash a 4-device
    sharded stream, resume it on 2 devices, and the drained results are
    bit-exact with an uninterrupted single-device run — surviving lanes
    and pending spans are re-dealt to the new mesh's shards (§9.12)."""
    cdir = str(tmp_path / "elastic-ck")
    crash = r"""
import json
from repro.fleet import engine
from test_fault_tolerance import _fleet_groups
import jax
mesh = jax.make_mesh((4,), ("fleet",))
try:
    engine.run_packed(_fleet_groups(), chunk=16, seg_steps=64,
                      keep_state=True, mesh=mesh,
                      checkpoint_dir=%(cdir)r, checkpoint_every=3,
                      _crash_after_segments=8)
    raise SystemExit("expected InjectedFault")
except engine.InjectedFault:
    pass
print(json.dumps({"ok": True}))
""" % {"cdir": cdir}
    resume = r"""
import json
import numpy as np
import jax
from repro.fleet import engine
from test_fault_tolerance import _FLEET_STATE_FIELDS, _fleet_groups
ref, ref_stats = engine.run_packed(_fleet_groups(), chunk=16,
                                   seg_steps=64, keep_state=True)
mesh = jax.make_mesh((2,), ("fleet",))
res, stats = engine.run_packed(_fleet_groups(), chunk=16, seg_steps=64,
                               keep_state=True, mesh=mesh,
                               checkpoint_dir=%(cdir)r,
                               checkpoint_every=3)
assert stats.n_shards == 2, stats.n_shards
# n_segments is NOT asserted across mesh shapes: per-shard lane
# occupancy (and so drain cadence) legitimately differs; bit-exact
# per-item results are the invariant
for a, b in zip(ref, res):
    for f in _FLEET_STATE_FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)
print(json.dumps({"ok": True}))
""" % {"cdir": cdir}
    for n_dev, script in ((4, crash), (2, resume)):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}")
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(_ROOT, "src"), _ROOT,
             os.path.join(_ROOT, "tests"), env.get("PYTHONPATH", "")])
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, (n_dev, proc.stderr[-2000:])
        assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]
    assert ckpt.latest_step(cdir) is not None


def test_compressed_allreduce_error_feedback():
    """EF-int8 all-reduce: single-step error bounded; residual carries the
    exact quantization error so the bias vanishes across steps."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(
        size=(64, 64)).astype(np.float32))}
    r = init_residuals(g)
    mean, r2 = compressed_allreduce(g, r, mesh, axis="data")
    # n=1: mean should equal dequantized(g), residual the rounding error
    err = np.abs(np.asarray(mean["w"]) - np.asarray(g["w"]))
    scale = np.abs(np.asarray(g["w"])).max() / 127
    assert err.max() <= scale * 0.51 + 1e-6
    np.testing.assert_allclose(np.asarray(r2["w"]),
                               np.asarray(g["w"] - mean["w"]), atol=1e-6)
    # feeding back the residual recovers the lost mass
    mean2, _ = compressed_allreduce(
        jax.tree.map(jnp.zeros_like, g), r2, mesh, axis="data")
    recovered = np.asarray(mean["w"]) + np.asarray(mean2["w"])
    err2 = np.abs(recovered - np.asarray(g["w"]))
    assert err2.max() < err.max() + 1e-6
