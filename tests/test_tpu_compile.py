"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU
v5e, described rather than attached: the fused ISS segment and the
resident refill swap at the mixed Table-2 fleet's real widths, the
segment at engine-padded lane counts, at the food-spoilage patch's
widths and at a four-chip shard's 64 lanes, the engine's segment runner
sharded over the four chips of a v5e:2x2 host, and the carbon-sweep
tile at the planner's tile size. Each compiled program must hold the
Mosaic kernel (`tpu_custom_call`). Nothing runs; the TPU compiler
refuses what interpret mode accepts (unsupported layouts, dtypes,
fast-memory use).

The topology is described inside a fixture, only once a test of this
file runs: describing it loads the TPU library, which one process at a
time may hold.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.fleet import engine
from repro.flexibench.base import all_workloads, get
from repro.flexibits import iss
from repro.flexibits.cycles import N_COST
from repro.kernels import carbon_sweep as csk
from repro.kernels.iss_stepper import iss_refill, iss_segment_banked


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent
    cache but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def fleet_widths():
    """(n_progs, bank_width, mem_words) of the packed bank holding all
    11 FlexiBench workloads."""
    ws = all_workloads()
    return (len(ws), max(len(w.program.code) for w in ws),
            max(w.total_mem_words for w in ws))


@pytest.fixture(scope="module")
def fs_widths():
    """The food-spoilage patch's bank: one row of FS code, FS memory."""
    w = get("FS")
    return 1, len(w.program.code), w.total_mem_words


def _abstract(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed_state(sharding, n_lanes, mem_words):
    def s(*shape, dtype=jnp.int32):
        return _abstract(sharding, shape, dtype)
    lanes = iss.ISSState(s(n_lanes, 16), s(n_lanes), s(n_lanes, mem_words),
                         s(n_lanes, dtype=jnp.bool_), s(n_lanes),
                         s(n_lanes), s(n_lanes, len(iss.MIX_CLASSES)),
                         s(n_lanes))
    return iss.PackedState(lanes, s(n_lanes), s(n_lanes))


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _segment_args(sharding, widths, n_lanes):
    n_progs, width, mem_words = widths
    return (_abstract(sharding, (n_progs, width)),
            _abstract(sharding, (n_progs,)),
            _abstract(sharding, (n_progs,)),
            _abstract(sharding, (n_progs, N_COST)),
            _packed_state(sharding, n_lanes, mem_words))


def _segment(bank, code_len, mem_len, cost, state):
    return iss_segment_banked(bank, code_len, state, seg_steps=4096,
                              mem_len=mem_len, cost=cost, interpret=False)


def test_segment_compiles_at_fleet_widths(one_chip, no_persistent_cache,
                                          fleet_widths):
    _assert_kernel(_segment, *_segment_args(one_chip, fleet_widths, 256))


def test_refill_compiles_at_fleet_widths(one_chip, no_persistent_cache,
                                         fleet_widths):
    _, _, mem_words = fleet_widths
    n = 256

    def refill(state, take, src, mems, prog, ms):
        return iss_refill(state, take, src, mems, prog, ms,
                          interpret=False)

    _assert_kernel(refill, _packed_state(one_chip, n, mem_words),
                   _abstract(one_chip, (n,), jnp.bool_),
                   _abstract(one_chip, (n,)),
                   _abstract(one_chip, (n, mem_words)),
                   _abstract(one_chip, (n,)), _abstract(one_chip, (n,)))


@pytest.mark.parametrize("chunk", [200, 96])
def test_segment_compiles_at_engine_padded_lanes(one_chip,
                                                 no_persistent_cache,
                                                 fleet_widths, chunk):
    """Pools the engine pads to 128-lane tiles (200 -> 256) or runs as
    one full-width block (96)."""
    n_lanes = engine._pool_lanes(chunk, "pallas", 1, False)
    assert n_lanes % 128 == 0 or n_lanes <= 128
    _assert_kernel(_segment, *_segment_args(one_chip, fleet_widths,
                                            n_lanes))


def test_segment_compiles_at_fs_widths(one_chip, no_persistent_cache,
                                      fs_widths):
    """The engine's 256-lane pool over a 1-row, 52-word bank and 128-word
    memory, timing on."""
    _assert_kernel(_segment, *_segment_args(one_chip, fs_widths, 256))


def test_segment_compiles_at_four_chip_shard(one_chip, no_persistent_cache,
                                             fleet_widths):
    """A four-chip shard of the 256-lane pool: 64 lanes, one full-width
    block."""
    n_lanes = engine._pool_lanes(256, "pallas", 4, False) // 4
    assert n_lanes == 64
    _assert_kernel(_segment, *_segment_args(one_chip, fleet_widths,
                                            n_lanes))


def test_sharded_segment_runner_compiles_on_four_chips(
        topo, no_persistent_cache, fleet_widths, monkeypatch):
    """The engine's Pallas segment runner under `shard_map` over a
    ("fleet",) mesh of the v5e:2x2 host's four chips, at the Table-2
    widths: each chip runs the kernel on its own 64 lanes, and the
    program holds no collective. The backend reads "tpu" while it is
    traced, so the kernel takes its compiled path, as on the chip."""
    if len(topo.devices) != 4:
        pytest.skip(f"v5e:2x2 described {len(topo.devices)} devices")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices), ("fleet",))
    n_progs, width, mem_words = fleet_widths
    n_lanes = engine._pool_lanes(256, "pallas", 4, False)
    seg = engine._packed_segment_runner("pallas", n_lanes, 4096, mem_words,
                                        n_progs, width, mesh, None, True)
    bank, code_len, mem_len, cost, state = _segment_args(
        NamedSharding(mesh, P()), fleet_widths, n_lanes)
    state = jax.tree.map(
        lambda a: _abstract(NamedSharding(mesh, P("fleet")), a.shape,
                            a.dtype), state)
    text = seg.lower(bank, code_len, mem_len, cost, state).compile() \
        .as_text()
    assert "tpu_custom_call" in text
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in text, op


@pytest.mark.parametrize("draws", [64, 128])
def test_sweep_tile_compiles(one_chip, no_persistent_cache, draws):
    n_cells, f32, i32 = 1024, jnp.float32, jnp.int32
    acc = csk.SweepAcc(*(_abstract(one_chip, a.shape, a.dtype)
                         for a in csk.init_acc(64, 32, f32)))

    def tile(emb, kwh, inten, freq, life, valid, cell, acc):
        return csk.sweep_tile(emb, kwh, inten, freq, life, valid, cell,
                              acc, hist_lo=-4.0, hist_inv=12.8,
                              par_lo=-4.0, par_inv=6.4, path="pallas",
                              interpret=False)

    _assert_kernel(tile, _abstract(one_chip, (n_cells, 3), f32),
                   _abstract(one_chip, (n_cells, 3), f32),
                   _abstract(one_chip, (n_cells,), f32),
                   _abstract(one_chip, (n_cells,), f32),
                   _abstract(one_chip, (n_cells, draws), f32),
                   _abstract(one_chip, (n_cells,), np.bool_),
                   _abstract(one_chip, (n_cells,), i32), acc)
