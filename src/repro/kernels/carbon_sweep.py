"""Fused carbon-sweep evaluate-and-reduce kernel (DESIGN.md §9.13).

The hot inner loop of the Monte Carlo carbon-planner sweep
(`core/sweep.py`): given one streamed tile of scenario cells — per-cell
(embodied, operational-anchor) rows over the candidate cores, the cell's
grid intensity and task frequency, and the tile's Monte Carlo lifetime
draws — evaluate the total-carbon surface over the core axis, select the
carbon-optimal core per scenario, and reduce everything the planner
reports *inside the tile*:

- per-cell over draws: sum/min/max of the best-core total, chosen-core
  counts, chosen embodied/operational sums (the percentile sort runs in
  the shared wrapper on the tile-sized best-total matrix — the full
  (cells x draws) tensor never exists);
- across the whole sweep: a log-binned histogram of best totals and a
  binned embodied-vs-operational Pareto frontier, both carried as small
  accumulator arrays that the engine streams through every tile.

Two interchangeable paths with ONE shared arithmetic pipeline
(`_totals` / `_cell_reduce` / `_hist_contrib` / `_pareto_candidate` /
`_pareto_merge`), following the `iss_stepper.py` contract that A/B paths
share their math so they cannot drift:

- `sweep_tile(..., path="jnp")`: pure-jnp broadcast over the whole tile
  (the bit-exact baseline);
- `sweep_tile(..., path="pallas")`: a `pl.pallas_call` gridded over row
  tiles of the cell axis, per-cell outputs block-mapped per row tile and
  the histogram/Pareto accumulators mapped to one shared block that
  every grid step revisits (initialized from the aliased running
  accumulator at step 0, then accumulated in place — the
  `input_output_aliases` idiom of `iss_stepper.py`). All accumulator
  updates are associative (int adds, lexicographic mins), so the
  sequential per-row-tile merges equal the jnp path's single whole-tile
  merge bit-for-bit, at any row-tile size.

Bit-exactness contract: for identical tile inputs, every output of the
two paths is bit-identical (pinned by tests/test_sweep.py); the totals
themselves are evaluated in exactly the numpy oracle's op order
(`core.selection.total_grid`: ``emb + (base * life_days) * freq`` with
``base = kwh * intensity``), so on point-mass lifetime draws the sweep
is bit-equal to the host planner grid as well.

As in `iss_stepper.py`, the kernel always compiles to Mosaic on a TPU
and defaults to the Pallas interpreter on any other backend. The
bit-exactness contract holds on the CPU; on the TPU the two paths lower
their float reductions differently (`chip_smoke.py` reports whether
they agree there).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

I32 = jnp.int32
_IMAX = jnp.iinfo(jnp.int32).max


def _pick_row_tile(n_rows: int) -> int:
    """Cells per grid step. Cells run along the TPU's sublane axis, so the
    tile is the largest multiple of 8 up to 128 that divides the cell
    count, or the whole tile when none does."""
    for d in range(128, 0, -8):
        if n_rows % d == 0:
            return d
    return n_rows


class SweepAcc(NamedTuple):
    """Streamed cross-tile accumulators (device-resident, donated).

    `hist` counts best-core totals into fixed log10 bins; the `par_*`
    arrays hold, per embodied-axis log10 bin, the lexicographically
    minimal (operational, cell, draw) point seen so far with its
    payload — the streamed Pareto frontier. Empty bins carry
    (+inf, IMAX, IMAX) sentinels.
    """
    hist: jax.Array       # (B,)  int32
    par_op: jax.Array     # (Bp,) dtype — min operational kg in bin
    par_emb: jax.Array    # (Bp,) dtype — embodied kg of that point
    par_life: jax.Array   # (Bp,) dtype — lifetime draw (days) of point
    par_cell: jax.Array   # (Bp,) int32 — global cell index
    par_draw: jax.Array   # (Bp,) int32 — draw index
    par_core: jax.Array   # (Bp,) int32 — chosen core index


class TileOut(NamedTuple):
    """Per-cell reductions for one streamed tile of scenario cells."""
    best_total: jax.Array  # (Tc, N) chosen-core total kg per draw
    best_core: jax.Array   # (Tc, N) int32 argmin core index
    counts: jax.Array      # (Tc, C) int32 chosen-core histogram
    sum_best: jax.Array    # (Tc,) sum over draws of best totals
    min_best: jax.Array    # (Tc,)
    max_best: jax.Array    # (Tc,)
    sum_emb: jax.Array     # (Tc,) sum of chosen embodied kg
    sum_op: jax.Array      # (Tc,) sum of chosen operational kg


def init_acc(n_hist: int, n_pareto: int, dtype) -> SweepAcc:
    inf = jnp.array(jnp.inf, dtype)
    return SweepAcc(
        hist=jnp.zeros((n_hist,), I32),
        par_op=jnp.full((n_pareto,), inf),
        par_emb=jnp.full((n_pareto,), inf),
        par_life=jnp.full((n_pareto,), inf),
        par_cell=jnp.full((n_pareto,), _IMAX, I32),
        par_draw=jnp.full((n_pareto,), _IMAX, I32),
        par_core=jnp.full((n_pareto,), _IMAX, I32),
    )


# --------------------------------------------------- shared arithmetic
# Layout shared by both paths: cells run down the rows, per-cell values
# are (Tc, 1) columns, draws run along the last axis, and the candidate
# axis (a handful of cores) is unrolled into lists of per-candidate
# arrays. Everything is 2-D, and the argmin, gathers and histogram are
# select chains and reductions, which Mosaic lowers.

def _cols(x):
    """(Tc, C) -> C per-candidate (Tc, 1) columns."""
    return [x[:, c:c + 1] for c in range(x.shape[1])]


def _place(vals, shape, dtype):
    """Scatter `vals[i]` (broadcastable) into column i of a `shape`
    array — the inverse of `_cols`, as a select chain."""
    iota = lax.broadcasted_iota(I32, shape, 1)
    out = jnp.zeros(shape, dtype)
    for i, v in enumerate(vals):
        out = jnp.where(iota == i, v, out)
    return out


def _totals(emb, kwh, inten, freq, life_days):
    """Per-candidate total and operational surfaces over (cells, draws).

    EXACTLY the numpy oracle's op order (`selection.total_grid`):
    ``base = kwh * intensity``; ``total = emb + (base * life_days) *
    freq`` — so point-mass draws reproduce the host grid bit-for-bit.
    `life_days` arrives pre-divided from the engine (`core/sweep.py`
    guards that division against XLA's f32 divide-by-constant ->
    reciprocal-multiply rewrite) so both A/B paths consume identical
    bits; the remaining ops here are pure multiply chains and a
    contraction-blocked add, which XLA CPU leaves bit-stable.
    """
    totals, ops = [], []
    for e, k in zip(_cols(emb), _cols(kwh)):
        op = ((k * inten) * life_days) * freq
        # `abs` is a bitwise identity here (op >= 0 always) whose only
        # job is to break the fadd(fmul) pattern: XLA CPU otherwise
        # contracts `emb + op` into an FMA, which rounds differently
        # from the numpy oracle's separate multiply-then-add
        totals.append(e + jnp.abs(op))
        ops.append(op)
    return totals, ops


def _cell_reduce(totals, ops, emb):
    """argmin core selection + per-cell reductions over the draw axis."""
    embs = _cols(emb)
    best_total, best_op = totals[0], ops[0]
    best_emb = jnp.broadcast_to(embs[0], best_total.shape)
    best_core = jnp.zeros(best_total.shape, I32)
    for c in range(1, len(totals)):
        better = totals[c] < best_total              # first-min ties
        best_total = jnp.where(better, totals[c], best_total)
        best_op = jnp.where(better, ops[c], best_op)
        best_emb = jnp.where(better, embs[c], best_emb)
        best_core = jnp.where(better, c, best_core)
    counts = [jnp.sum((best_core == c).astype(I32), axis=1, keepdims=True)
              for c in range(len(totals))]
    return TileOut(
        best_total=best_total,
        best_core=best_core,
        counts=_place(counts, emb.shape, I32),
        sum_best=jnp.sum(best_total, axis=1, keepdims=True),
        min_best=jnp.min(best_total, axis=1, keepdims=True),
        max_best=jnp.max(best_total, axis=1, keepdims=True),
        sum_emb=jnp.sum(best_emb, axis=1, keepdims=True),
        sum_op=jnp.sum(best_op, axis=1, keepdims=True),
    ), best_op


def _log_bin(x, lo, inv, n_bins):
    """Fixed log10 binning; out-of-range values clamp to the end bins."""
    b = jnp.floor((jnp.log10(x) - lo) * inv).astype(I32)
    return jnp.clip(b, 0, n_bins - 1)


def _hist_contrib(best_total, valid, lo, inv, n_bins):
    """(1, B) histogram of the tile's valid best totals: one masked
    count per bin (integer adds are exact, so any grouping of cells
    into tiles sums to the same counts)."""
    bins = _log_bin(best_total, lo, inv, n_bins)        # (Tc, N)
    hits = [jnp.sum(((bins == b) & valid).astype(I32))
            for b in range(n_bins)]
    return _place(hits, (1, n_bins), I32)


def _pareto_candidate(emb, best_op, life_days, cell_idx, best_core,
                      valid, lo, inv, n_bins):
    """Per-bin lexicographic min over this tile's scenario points.

    Global key order is (operational, cell, draw); the chosen core is a
    pure function of (cell, draw), so the key is a strict total order
    and per-bin min is associative — any grouping of scenarios into row
    tiles merges to the same frontier.

    Reduced in two levels: all draws of one (cell, core) share the same
    embodied kg and therefore the same bin, so first each (cell, core)
    group elects its champion draw (min op, then min draw — over the
    draws that actually chose that core), then the per-bin min runs
    over the (cells x cores) champions instead of (cells x draws)
    scenarios. A lexicographic min over any partition equals the global
    min, so this is bit-identical to the flat reduction. Returns (1, B)
    rows.
    """
    n_cells = best_op.shape[0]
    inf = jnp.array(jnp.inf, best_op.dtype)
    iota_draw = lax.broadcasted_iota(I32, best_op.shape, 1)
    iota_bin = lax.broadcasted_iota(I32, (n_cells, n_bins), 1)
    embs = _cols(emb)

    # level 1: per-(cell, core) champion draw, (Tc, 1) columns
    masks, opbs, draws, lives = [], [], [], []
    for c, e in enumerate(embs):
        chose = best_core == c
        opm = jnp.where(chose, best_op, inf)
        op_cc = jnp.min(opm, axis=1, keepdims=True)
        tie = chose & (opm == op_cc)
        drawm = jnp.where(tie, iota_draw, _IMAX)
        draw_cc = jnp.min(drawm, axis=1, keepdims=True)
        tie = tie & (drawm == draw_cc)                  # exactly one draw
        lives.append(jnp.sum(jnp.where(tie, life_days, 0), axis=1,
                             keepdims=True))
        draws.append(draw_cc)
        alive = valid & (op_cc < inf)
        mask = (iota_bin == _log_bin(e, lo, inv, n_bins)) & alive
        masks.append(mask)                              # (Tc, B)
        opbs.append(jnp.where(mask, op_cc, inf))

    # level 2: per-bin lexicographic min over the champions, (1, B) rows
    def col_min(vals):
        out = jnp.min(vals[0], axis=0, keepdims=True)
        for v in vals[1:]:
            out = jnp.minimum(out, jnp.min(v, axis=0, keepdims=True))
        return out

    op_min = col_min(opbs)
    # bins that are empty OR whose best point overflowed to +inf both
    # keep the (inf, IMAX, IMAX) sentinel record
    finite = op_min < inf
    ties = [m & (o == op_min) & finite for m, o in zip(masks, opbs)]
    cellm = [jnp.where(t, cell_idx, _IMAX) for t in ties]
    cell_min = col_min(cellm)
    ties = [t & (m == cell_min) for t, m in zip(ties, cellm)]
    drawb = [jnp.where(t, d, _IMAX) for t, d in zip(ties, draws)]
    draw_min = col_min(drawb)
    ties = [t & (m == draw_min) for t, m in zip(ties, drawb)]

    def pick(vals, empty):
        # `ties` selects exactly one champion per bin with a finite best
        # point; sentinel bins sum to 0 and take `empty`
        out = jnp.where(finite, 0, empty)
        for t, v in zip(ties, vals):
            out = out + jnp.sum(jnp.where(t, v, 0), axis=0, keepdims=True)
        return out

    return (jnp.where(finite, op_min, inf), pick(embs, inf),
            pick(lives, inf), jnp.where(finite, cell_min, _IMAX),
            jnp.where(finite, draw_min, _IMAX),
            pick(list(range(len(embs))), _IMAX).astype(I32))


def _pareto_merge(a: Tuple, b: Tuple) -> Tuple:
    """Elementwise lexicographic-min merge of two per-bin frontiers."""
    a_op, a_emb, a_life, a_cell, a_draw, a_core = a
    b_op, b_emb, b_life, b_cell, b_draw, b_core = b
    take_b = (b_op < a_op) \
        | ((b_op == a_op) & (b_cell < a_cell)) \
        | ((b_op == a_op) & (b_cell == a_cell) & (b_draw < a_draw))
    w = jnp.where
    return (w(take_b, b_op, a_op), w(take_b, b_emb, a_emb),
            w(take_b, b_life, a_life), w(take_b, b_cell, a_cell),
            w(take_b, b_draw, a_draw), w(take_b, b_core, a_core))


def _eval_tile(emb, kwh, inten, freq, life_days, valid, cell_idx, *,
               hist_lo, hist_inv, par_lo, par_inv, n_hist, n_pareto):
    """Shared per-(sub)tile pipeline used verbatim by both paths."""
    totals, ops = _totals(emb, kwh, inten, freq, life_days)
    out, best_op = _cell_reduce(totals, ops, emb)
    hist = _hist_contrib(out.best_total, valid, hist_lo, hist_inv, n_hist)
    cand = _pareto_candidate(emb, best_op, life_days, cell_idx,
                             out.best_core, valid, par_lo, par_inv,
                             n_pareto)
    return out, hist, cand


# ------------------------------------------------------------ jnp path
def _sweep_tile_jnp(emb, kwh, inten, freq, life_days, valid, cell_idx,
                    acc: SweepAcc, **kw):
    out, hist, cand = _eval_tile(emb, kwh, inten, freq, life_days,
                                 valid != 0, cell_idx, **kw)
    par = _pareto_merge(tuple(acc[1:]), cand)
    return out, SweepAcc(acc.hist + hist, *par)


# --------------------------------------------------------- pallas path
def _sweep_kernel(emb_ref, kwh_ref, inten_ref, freq_ref, life_ref,
                  valid_ref, cell_ref, hist_in_ref, *par_refs, **kw):
    """One row tile of the cell axis; every grid step merges its
    histogram/Pareto contribution into the shared accumulator block."""
    (pop_in, pemb_in, plife_in, pcell_in, pdraw_in, pcore_in,
     bt_ref, bc_ref, cnt_ref, sb_ref, mn_ref, mx_ref, se_ref, so_ref,
     ohist_ref, oop_ref, oemb_ref, olife_ref, ocell_ref, odraw_ref,
     ocore_ref) = par_refs

    out, hist, cand = _eval_tile(
        emb_ref[...], kwh_ref[...], inten_ref[...], freq_ref[...],
        life_ref[...], valid_ref[...] != 0, cell_ref[...], **kw)
    bt_ref[...] = out.best_total
    bc_ref[...] = out.best_core
    cnt_ref[...] = out.counts
    sb_ref[...] = out.sum_best
    mn_ref[...] = out.min_best
    mx_ref[...] = out.max_best
    se_ref[...] = out.sum_emb
    so_ref[...] = out.sum_op

    @pl.when(pl.program_id(0) == 0)
    def _seed_accumulators():
        ohist_ref[...] = hist_in_ref[...]
        oop_ref[...] = pop_in[...]
        oemb_ref[...] = pemb_in[...]
        olife_ref[...] = plife_in[...]
        ocell_ref[...] = pcell_in[...]
        odraw_ref[...] = pdraw_in[...]
        ocore_ref[...] = pcore_in[...]

    ohist_ref[...] = ohist_ref[...] + hist
    cur = (oop_ref[...], oemb_ref[...], olife_ref[...], ocell_ref[...],
           odraw_ref[...], ocore_ref[...])
    mop, memb, mlife, mcell, mdraw, mcore = _pareto_merge(cur, cand)
    oop_ref[...] = mop
    oemb_ref[...] = memb
    olife_ref[...] = mlife
    ocell_ref[...] = mcell
    odraw_ref[...] = mdraw
    ocore_ref[...] = mcore


def _sweep_tile_pallas(emb, kwh, inten, freq, life_days, valid,
                       cell_idx, acc: SweepAcc,
                       interpret=None, **kw):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_cells, n_draws = life_days.shape
    n_cores = emb.shape[1]
    dtype = life_days.dtype
    rt = _pick_row_tile(n_cells)

    def rows(width):
        return pl.BlockSpec((rt, width), lambda i: (i, 0))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0, 0))

    outs = pl.pallas_call(
        functools.partial(_sweep_kernel, **kw),
        grid=(n_cells // rt,),
        in_specs=[rows(n_cores), rows(n_cores), rows(1), rows(1),
                  rows(n_draws), rows(1), rows(1)]
        + [whole(a) for a in acc],
        out_specs=[rows(n_draws), rows(n_draws), rows(n_cores)]
        + [rows(1)] * 5 + [whole(a) for a in acc],
        out_shape=[
            jax.ShapeDtypeStruct((n_cells, n_draws), dtype),   # best_total
            jax.ShapeDtypeStruct((n_cells, n_draws), I32),     # best_core
            jax.ShapeDtypeStruct((n_cells, n_cores), I32),     # counts
        ] + [jax.ShapeDtypeStruct((n_cells, 1), dtype)] * 5
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in acc],
        # running accumulators update in place (inputs 7-13 -> outputs
        # 8-14), the iss_stepper donation/aliasing idiom
        input_output_aliases={7 + j: 8 + j for j in range(len(acc))},
        interpret=interpret,
    )(emb, kwh, inten, freq, life_days, valid, cell_idx, *acc)
    return TileOut(*outs[:8]), SweepAcc(*outs[8:])


def sweep_tile(emb, kwh, inten, freq, life_days, valid, cell_idx,
               acc: SweepAcc, *, hist_lo: float, hist_inv: float,
               par_lo: float, par_inv: float, path: str = "jnp",
               interpret: Optional[bool] = None):
    """Evaluate-and-reduce one streamed tile of scenario cells.

    Inputs are per-cell rows over the core axis (`emb`/`kwh`, kg CO2e
    and intensity-1 kWh-rate anchors), per-cell scalars (`inten` kg/kWh,
    `freq` execs/day), and the tile's Monte Carlo lifetime draws
    (`life_days`, days, (cells, draws) — pre-divided by the engine so
    both paths see identical bits). `valid` masks padded cells out of
    the global accumulators; `cell_idx` is the global cell index used as
    the deterministic Pareto tie-break key. Returns `(TileOut, SweepAcc)`
    — per-cell reductions plus the advanced running accumulators.

    `path="jnp"` is the pure-broadcast baseline; `path="pallas"` runs
    the same pipeline as one kernel gridded over row tiles (compiled on
    a TPU, interpreted elsewhere unless `interpret` says otherwise). The
    paths are bit-identical for identical inputs on CPU
    (tests/test_sweep.py).
    """
    if path not in ("jnp", "pallas"):
        raise ValueError(f"unknown sweep path {path!r} "
                         f"(expected 'jnp' or 'pallas')")
    kw = dict(hist_lo=hist_lo, hist_inv=hist_inv, par_lo=par_lo,
              par_inv=par_inv, n_hist=acc.hist.shape[0],
              n_pareto=acc.par_op.shape[0])
    # the shared pipeline's 2-D layout: per-cell columns, (1, B) rows
    cols = (inten[:, None], freq[:, None], life_days,
            valid.astype(I32)[:, None], cell_idx.astype(I32)[:, None])
    acc2 = SweepAcc(*(a[None] for a in acc))
    if path == "jnp":
        out, acc2 = _sweep_tile_jnp(emb, kwh, *cols, acc2, **kw)
    else:
        out, acc2 = _sweep_tile_pallas(emb, kwh, *cols, acc2,
                                       interpret=interpret, **kw)
    out = out._replace(**{f: getattr(out, f)[:, 0] for f in
                          ("sum_best", "min_best", "max_best", "sum_emb",
                           "sum_op")})
    return out, SweepAcc(*(a[0] for a in acc2))
