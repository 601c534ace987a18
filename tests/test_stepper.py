"""Lane-parallel branchless stepper and fused-segment pallas stepper
(DESIGN.md §9.5/§9.6/§9.7): bit-exactness vs the lax.switch interpreter
over a randomized instruction soup covering every opcode class,
opcode-subset specialization, segment-loop parity, engine stepper A/B
parity, the async prefetcher, and sharded multi-device streaming."""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.flexibits import isa, iss
from repro.kernels.iss_stepper import iss_segment

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
MEM_WORDS = 64


def _random_instr(rng, name):
    rd = int(rng.integers(0, 16))
    rs1 = int(rng.integers(0, 16))
    rs2 = int(rng.integers(0, 16))
    imm = int(rng.integers(-2048, 2048))
    if name in isa.SHIFT_OPS:
        imm = int(rng.integers(0, 32))
    elif name in isa.B_OPS or name == "jal":
        imm = int(rng.integers(-64, 64)) * 2
    elif name in ("lui", "auipc"):
        imm = int(rng.integers(0, 1 << 20))
    elif name in ("lb", "lh", "lw", "lbu", "lhu", "sb", "sh", "sw"):
        imm = int(rng.integers(0, MEM_WORDS * 4 - 4))
    return isa.encode(name, rd=rd, rs1=rs1, rs2=rs2, imm=imm)


def _random_state(rng, mem_like=False):
    regs = rng.integers(-2**31, 2**31, 16).astype(np.int64)
    if mem_like:     # keep addresses near the memory (including OOB edges)
        regs = np.abs(regs) % (MEM_WORDS * 2)
    regs[0] = 0
    mem = rng.integers(-2**31, 2**31, MEM_WORDS).astype(np.int64)
    s = iss.init_state(jnp.asarray(mem.astype(np.int32)))
    return s._replace(regs=jnp.asarray(regs.astype(np.int32)))


def _assert_state_equal(a: iss.ISSState, b: iss.ISSState, ctx=""):
    for f in iss.ISSState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{ctx}: field {f}")


def test_branchless_step_bit_exact_instruction_soup():
    """Every opcode class x random fields x random state: step_branchless
    commits exactly what the lax.switch step commits."""
    rng = np.random.default_rng(7)
    step = jax.jit(iss.step)
    step_bl = jax.jit(iss.step_branchless)
    mem_ops = ("lb", "lh", "lw", "lbu", "lhu", "sb", "sh", "sw")
    for name in isa.ALL_OPS:
        for _ in range(8):
            word = _random_instr(rng, name)
            code = jnp.asarray(np.array([word], np.uint32).view(np.int32))
            s = _random_state(rng, mem_like=name in mem_ops)
            _assert_state_equal(step(code, s), step_bl(code, s),
                                ctx=f"{name} word={word:#010x}")


def test_step_lanes_bit_exact_batched_soup():
    """step_lanes over a lane batch == vmap(step), one random instruction
    per lane drawn from the full ISA."""
    rng = np.random.default_rng(11)
    lanes = len(isa.ALL_OPS)
    words = np.array([_random_instr(rng, n) for n in isa.ALL_OPS],
                     np.uint32)
    # each lane points at its own instruction in a shared program
    states = []
    for i in range(lanes):
        s = _random_state(rng)
        states.append(s._replace(pc=jnp.asarray(4 * i, iss.I32)))
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    code = jnp.asarray(words.view(np.int32))
    ref = jax.jit(jax.vmap(lambda s: iss.step(code, s)))(batched)
    got = jax.jit(lambda st: iss.step_lanes(code, st))(batched)
    _assert_state_equal(ref, got, ctx="batched soup")


def test_opcode_subset_is_sound_and_minimal():
    from repro.flexibench.base import get
    w = get("WQ")
    sub = iss.opcode_subset(w.program.code)
    # sound: every opcode the program retires is in the subset
    assert sub <= iss.FULL_SUBSET
    ops_in_text = {int(x) & 0x7F
                   for x in w.program.code.view(np.uint32).tolist()}
    assert {o for o in ops_in_text if o in iss.FULL_SUBSET} == set(sub)


def test_subset_specialized_segment_parity():
    """run_segment_lanes with the derived opcode subset retires the exact
    sequence of the full-ISA switch interpreter on a real workload."""
    from repro.flexibench.base import get
    from repro.flexibits.fleet import fleet_inputs
    w = get("MC")
    n = 12
    mems = fleet_inputs(w, n, seed=9)
    code = jnp.asarray(w.program.code.view(np.int32))
    sub = iss.opcode_subset(w.program.code)
    mono = iss.run_fleet(code, jnp.asarray(mems), w.max_steps)

    states = iss.ISSState(
        regs=jnp.zeros((n, 16), iss.I32),
        pc=jnp.zeros((n,), iss.I32),
        mem=jnp.asarray(mems),
        halted=jnp.zeros((n,), bool),
        n_instr=jnp.zeros((n,), iss.I32),
        n_two_stage=jnp.zeros((n,), iss.I32),
        mix=jnp.zeros((n, len(iss.MIX_CLASSES)), iss.I32),
        n_cycles=jnp.zeros((n,), iss.I32),
    )
    seg = jax.jit(lambda c, st: iss.run_segment_lanes(
        c, st, 64, w.max_steps, sub))
    for _ in range(10_000):
        states = seg(code, states)
        if bool(np.asarray(states.halted).all()):
            break
    _assert_state_equal(states, mono, ctx="subset segment")


def test_segment_unroll_bit_exact():
    """Unrolled segment bodies mask sub-steps past seg_steps, so any
    (seg_steps, unroll) combination retires the same sequence."""
    from repro.flexibench.base import get
    from repro.flexibits.fleet import fleet_inputs
    w = get("WQ")
    mems = fleet_inputs(w, 6, seed=1)
    code = jnp.asarray(w.program.code.view(np.int32))
    states = iss.ISSState(
        regs=jnp.zeros((6, 16), iss.I32), pc=jnp.zeros((6,), iss.I32),
        mem=jnp.asarray(mems), halted=jnp.zeros((6,), bool),
        n_instr=jnp.zeros((6,), iss.I32),
        n_two_stage=jnp.zeros((6,), iss.I32),
        mix=jnp.zeros((6, len(iss.MIX_CLASSES)), iss.I32),
        n_cycles=jnp.zeros((6,), iss.I32))
    ref = jax.jit(lambda c, s: iss.run_segment_lanes(
        c, s, 37, w.max_steps))(code, states)
    got = jax.jit(lambda c, s: iss.run_segment_lanes(
        c, s, 37, w.max_steps, None, 8))(code, states)
    _assert_state_equal(ref, got, ctx="unroll=8 vs 1, seg_steps=37")
    assert int(np.asarray(got.n_instr).max()) <= 37


def test_pallas_segment_bit_exact_instruction_soup():
    """Every opcode class x random fields x random state: the fused
    pallas segment at seg_steps=1 commits exactly what step_lanes
    commits — including clamp-on-read / drop-on-write behavior at the
    OOB memory edges the mem-op lane states are biased toward."""
    rng = np.random.default_rng(21)
    mem_ops = ("lb", "lh", "lw", "lbu", "lhu", "sb", "sh", "sw")
    # 256 lanes (two 128-lane tiles) exercise the lane-tile grid as well;
    # lane i runs its own random word of opcode i % len(ALL_OPS)
    names = [isa.ALL_OPS[i % len(isa.ALL_OPS)] for i in range(256)]
    for trial in range(6):
        words = np.array([_random_instr(rng, n) for n in names],
                         np.uint32)
        states = []
        for i, name in enumerate(names):
            s = _random_state(rng, mem_like=name in mem_ops)
            states.append(s._replace(pc=jnp.asarray(4 * i, iss.I32)))
        batched = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        code = jnp.asarray(words.view(np.int32))
        ref = jax.jit(lambda st: iss.step_lanes(code, st))(batched)
        got = iss_segment(code, batched, seg_steps=1, max_steps=1 << 30)
        _assert_state_equal(ref, got, ctx=f"pallas soup trial {trial}")


def test_pallas_subset_segment_parity():
    """Fused segments with the derived opcode subset retire the exact
    sequence of the monolithic full-ISA interpreter on a real workload,
    across many segment boundaries and a tiled lane grid (256 lanes,
    two 128-lane tiles)."""
    from repro.flexibench.base import get
    from repro.flexibits.fleet import fleet_inputs
    w = get("MC")
    n = 256
    mems = fleet_inputs(w, n, seed=9)
    code = jnp.asarray(w.program.code.view(np.int32))
    sub = iss.opcode_subset(w.program.code)
    mono = iss.run_fleet(code, jnp.asarray(mems), w.max_steps)

    states = iss.ISSState(
        regs=jnp.zeros((n, 16), iss.I32),
        pc=jnp.zeros((n,), iss.I32),
        mem=jnp.asarray(mems),
        halted=jnp.zeros((n,), bool),
        n_instr=jnp.zeros((n,), iss.I32),
        n_two_stage=jnp.zeros((n,), iss.I32),
        mix=jnp.zeros((n, len(iss.MIX_CLASSES)), iss.I32),
        n_cycles=jnp.zeros((n,), iss.I32),
    )
    seg = jax.jit(lambda c, st: iss_segment(
        c, st, seg_steps=64, max_steps=w.max_steps, subset=sub))
    for _ in range(10_000):
        states = seg(code, states)
        if bool(np.asarray(states.halted).all()):
            break
    _assert_state_equal(states, mono, ctx="pallas subset segment")


def test_engine_stepper_ab_parity():
    """run_stream is bit-exact across all three steppers (switch,
    branchless, pallas), including full final state and the engine's
    lane-step accounting."""
    from benchmarks.fleet import skew_fleet, skew_program
    from repro.fleet import array_source, run_stream
    prog = skew_program()
    mems = skew_fleet(prog, 48, short_iters=8, long_iters=900,
                      long_frac=0.25, seed=5)
    kw = dict(n_items=48, mem_words=32, max_steps=100_000, chunk=16,
              seg_steps=64, out_addr=1, keep_state=True)
    a = run_stream(prog.code, array_source(mems), stepper="switch", **kw)
    assert a.stepper == "switch"
    for stepper in ("branchless", "pallas"):
        b = run_stream(prog.code, array_source(mems), stepper=stepper,
                       **kw)
        np.testing.assert_array_equal(a.mems, b.mems)
        np.testing.assert_array_equal(a.regs, b.regs)
        np.testing.assert_array_equal(a.n_instr, b.n_instr)
        np.testing.assert_array_equal(a.out, b.out)
        np.testing.assert_array_equal(a.mix, b.mix)
        assert a.lane_steps == b.lane_steps
        assert b.stepper == stepper


def test_prefetcher_preserves_stream_order():
    from repro.fleet.engine import _Prefetcher

    def source(start, count):
        return np.arange(start, start + count, dtype=np.int32)[:, None]

    for background in (True, False):
        pref = _Prefetcher(source, 103, block=16, background=background)
        got = np.concatenate([pref.take(7) for _ in range(14)]
                             + [pref.take(5)])
        np.testing.assert_array_equal(got[:, 0], np.arange(103))
        pref.close()


def test_pallas_prime_chunk_rounds_to_wide_tiles():
    """A prime chunk > 128 would tile at 1 lane/kernel; the engine pads
    the pallas lane pool up to a 128-multiple instead (inert padding
    lanes), staying bit-exact with branchless."""
    from benchmarks.fleet import skew_fleet, skew_program
    from repro.fleet import array_source, run_stream
    prog = skew_program()
    mems = skew_fleet(prog, 140, short_iters=8, long_iters=200,
                      long_frac=0.2, seed=3)
    kw = dict(n_items=140, mem_words=32, max_steps=100_000, chunk=131,
              seg_steps=64, out_addr=1)
    a = run_stream(prog.code, array_source(mems), stepper="branchless",
                   **kw)
    b = run_stream(prog.code, array_source(mems), stepper="pallas", **kw)
    assert b.chunk == 256 and a.chunk == 131
    np.testing.assert_array_equal(a.out, b.out)
    np.testing.assert_array_equal(a.n_instr, b.n_instr)


def test_prefetcher_exhaustion_reports_cursor_and_counts():
    """Over-draining the stream raises a diagnostic error naming the
    cursor, the requested count, and n_items (regression: the bare
    'source stream exhausted' gave nothing to debug a plan/source
    n_items mismatch with) — in both sync and background modes."""
    from repro.fleet.engine import _Prefetcher

    def source(start, count):
        return np.zeros((count, 1), np.int32)

    for background in (True, False):
        pref = _Prefetcher(source, 10, block=4, background=background)
        pref.take(7)
        with pytest.raises(RuntimeError) as exc:
            pref.take(5)
        msg = str(exc.value)
        assert "requested 5" in msg and "cursor 7" in msg
        assert "10 item(s)" in msg and "3 item(s) remaining" in msg
        pref.take(3)          # the remainder is still deliverable
        pref.close()


def test_prefetcher_close_drains_inflight_fetch():
    """close() must cancel or drain the background fetch: a leaked
    worker thread must never still be inside the source after close()
    returns (regression: shutdown(wait=False) left it running)."""
    from repro.fleet.engine import _Prefetcher
    lock = threading.Lock()
    running = [0]
    calls = []

    def source(start, count):
        with lock:
            running[0] += 1
        calls.append(start)
        time.sleep(0.2)
        with lock:
            running[0] -= 1
        return np.zeros((count, 1), np.int32)

    pref = _Prefetcher(source, 64, block=16, background=True)
    pref.close()
    assert running[0] == 0, "source still running after close()"
    assert pref._fut is None
    n_calls = len(calls)
    time.sleep(0.3)          # a cancelled future must never fire late
    assert len(calls) == n_calls <= 1


def test_prefetcher_surfaces_background_exception_with_context():
    """A source that dies inside the worker thread must fail the NEXT
    take() (not vanish with the future) with the source, item span, and
    stream cursor in the message; every later take() keeps failing with
    the original exception chained — in both background and sync modes."""
    from repro.fleet.engine import _Prefetcher

    class Boom(ValueError):
        pass

    def source(start, count):
        if start >= 4:
            raise Boom(f"payload for [{start}:{start + count})")
        return np.zeros((count, 1), np.int32)

    for background in (True, False):
        pref = _Prefetcher(source, 64, block=4, background=background)
        pref.take(4)             # first block is healthy
        with pytest.raises(RuntimeError) as exc:
            pref.take(4)         # consumes the poisoned fetch
        msg = str(exc.value)
        assert "[4:8)" in msg and "cursor" in msg and "source" in msg
        assert isinstance(exc.value.__cause__, Boom)
        with pytest.raises(RuntimeError, match="already failed") as exc2:
            pref.take(1)         # latched: the stream stays dead
        assert isinstance(exc2.value.__cause__, Boom)
        pref.close()


def test_prefetcher_close_is_idempotent():
    """close() on every engine exit path means it can run twice (e.g.
    once in an except block, once in finally) — the second call must be
    a no-op, and take() after close() must fail loudly, not fall back
    to a synchronous fetch."""
    from repro.fleet.engine import _Prefetcher

    def source(start, count):
        return np.zeros((count, 1), np.int32)

    for background in (True, False):
        pref = _Prefetcher(source, 16, block=4, background=background)
        pref.take(2)
        pref.close()
        pref.close()             # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            pref.take(1)


def test_engine_prefetch_off_matches_on():
    from repro.flexibench.base import get
    from repro.fleet import run_workload_stream
    w = get("WQ")
    a = run_workload_stream(w, 20, seed=3, chunk=8, seg_steps=128,
                            prefetch=True)
    b = run_workload_stream(w, 20, seed=3, chunk=8, seg_steps=128,
                            prefetch=False)
    np.testing.assert_array_equal(a.out, b.out)
    np.testing.assert_array_equal(a.n_instr, b.n_instr)


@pytest.mark.slow
def test_sharded_multi_device_bit_exact():
    """shard_map streaming over 4 forced host devices stays bit-exact.

    jax pins the device count at first backend init, so this runs in a
    subprocess with --xla_force_host_platform_device_count."""
    script = r"""
import numpy as np, jax, jax.numpy as jnp, json
from benchmarks.fleet import skew_fleet, skew_program
from repro.fleet import array_source, run_stream
from repro.flexibits import iss
prog = skew_program()
mems = skew_fleet(prog, 64, short_iters=8, long_iters=400,
                  long_frac=0.2, seed=13)
mono = iss.run_fleet(jnp.asarray(prog.code.view(np.int32)),
                     jnp.asarray(mems), 100_000)
mesh = jax.make_mesh((len(jax.devices()),), ("fleet",))
for stepper in ("branchless", "pallas", "switch"):
    res = run_stream(prog.code, array_source(mems), n_items=64,
                     mem_words=32, max_steps=100_000, chunk=16,
                     seg_steps=64, out_addr=1, keep_state=True,
                     mesh=mesh, stepper=stepper)
    np.testing.assert_array_equal(res.mems, np.asarray(mono.mem))
    np.testing.assert_array_equal(res.n_instr, np.asarray(mono.n_instr))
    assert res.n_devices == 4, res.n_devices
print(json.dumps({"ok": True}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]
