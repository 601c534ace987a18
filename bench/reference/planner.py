"""Plain Monte Carlo carbon planner: the planner cells' reference.

The carbon model of paper section 5.4 in float64 numpy, over the whole
scenario space at once, with the answer a planner what-if returns: per
cell the carbon-optimal core of every lifetime draw and the mean,
percentiles, extremes and embodied/operational split of the best total;
the histogram of best totals over fixed log10 bins; and, per log10 bin
of embodied carbon, the least operational carbon among the scenarios
whose chosen core falls in it (the Pareto front).

Inputs are data: the per-workload profiles and certified worst-case
cycles of the configuration, the core table of Table 7, and the
what-if's axes. Lifetime draws come from counter-based uniforms,
`fold_in(PRNGKey(seed), cell)` then a (draws, 2) float32 draw, made
with JAX's threefry generator on the host CPU.

`dtype` other than float64 rounds every stage to that type: the
control of the planner cells runs it in bfloat16.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtri

DAY_S = 86_400.0
POINT, LOGNORMAL, WEIBULL = 0, 1, 2
# a draw whose best and second-best totals lie closer than this may
# choose either core, and a total this close to an inner histogram edge
# may fall in either bin: about seven times the largest relative error
# of a float32 answer on the chip (1.4e-4, PERF.md section 4)
TIE_RTOL = 1e-3
PCTS = (50, 90, 99)
FIELDS = ("mean", "p50", "p90", "p99", "min", "max", "mean_emb", "mean_op",
          "fleet_mean")

# Table 8 memory anchors and the embodied calibration (paper section 5.4;
# the wafer footprint reproduces Table 5's 0.01086 kg for the FS patch)
_SRAM_AREA_PER_KB = (661.85 - 2.32) / (40.0 - 0.01)
_SRAM_AREA_BASE = 2.32 - _SRAM_AREA_PER_KB * 0.01
_SRAM_MW_PER_KB = (642.58 - 2.26) / (40.0 - 0.01)
_SRAM_MW_BASE = 2.26 - _SRAM_MW_PER_KB * 0.01
_LPROM_AREA_PER_KB = 182.03 / 63.38
_AREA_UNIT_MM2 = 0.01
_KG_PER_MM2 = 33.4 / (27_000.0 * 0.9)
_TICKS_PER_CYCLE = 20


def _cost_row(core: dict, dynamic: bool) -> np.ndarray:
    w = int(core["width"])
    row = np.zeros(19)
    row[:8] = 640 // w + round(_TICKS_PER_CYCLE * core["a"])
    row[8:16] = 1280 // w + round(_TICKS_PER_CYCLE * core["b"])
    if dynamic:
        row[16], row[17], row[18] = 640 // w, 20 // w, 640 // w
    return row


def embodied_kg(core: dict, prof: dict) -> float:
    sram = max(_SRAM_AREA_BASE + _SRAM_AREA_PER_KB * prof["vm_kb"], 0.1)
    area = core["area_mm2"] + (sram + _LPROM_AREA_PER_KB * prof["nvm_kb"]) \
        * _AREA_UNIT_MM2
    return area * _KG_PER_MM2


def kwh_per_exec(core: dict, prof: dict, cycles: float,
                 clock_hz: float) -> float:
    power_mw = core["power_mw"] + max(
        _SRAM_MW_BASE + _SRAM_MW_PER_KB * prof["vm_kb"], 0.05)
    return power_mw * 1e-3 * cycles / clock_hz / 3.6e6


def tables(config: dict, whatif: dict):
    """emb[w, c] (kg) and kwh[t, w, c] (kWh per execution)."""
    plan = config["planner"]
    keys = list(plan["profiles"])
    cores = list(config["cores"])
    emb = np.empty((len(keys), len(cores)))
    kwh = np.empty((len(whatif["timing"]), len(keys), len(cores)))
    for wi, k in enumerate(keys):
        prof = plan["profiles"][k]
        ev = np.asarray(prof["events"], np.float64)
        for ci, name in enumerate(cores):
            core = config["cores"][name]
            emb[wi, ci] = embodied_kg(core, prof)
            for ti, mode in enumerate(whatif["timing"]):
                if mode == "wcet":
                    cyc = plan["wcet_cycles"][k][name]
                else:
                    cyc = float(ev @ _cost_row(core, mode == "dynamic")) \
                        / _TICKS_PER_CYCLE
                kwh[ti, wi, ci] = kwh_per_exec(core, prof, cyc,
                                               config["clock_hz"])
    return emb, kwh


def dist_comps(d: dict):
    """(kind, p1, p2, weight) rows, weights normalised."""
    rows = []
    for c in d["comps"]:
        if c["kind"] == "point":
            rows.append((POINT, c["days"] * DAY_S, 0.0, c.get("weight", 1)))
        elif c["kind"] == "lognormal":
            rows.append((LOGNORMAL, math.log(c["median_days"] * DAY_S),
                         c["sigma"], c.get("weight", 1)))
        else:
            rows.append((WEIBULL, c["scale_days"] * DAY_S, c["shape"],
                         c.get("weight", 1)))
    tot = sum(r[3] for r in rows)
    return [(k, a, b, w / tot) for k, a, b, w in rows]


def support_max(comps) -> float:
    hi = 0.0
    for kind, p1, p2, _ in comps:
        if kind == POINT:
            hi = max(hi, p1)
        elif kind == LOGNORMAL:
            hi = max(hi, math.exp(p1 + 8.0 * p2))
        else:
            hi = max(hi, p1 * 30.0 ** (1.0 / p2))
    return hi


def uniforms(seed: int, n_cells: int, draws: int) -> np.ndarray:
    """(cells, draws, 2) float32 uniforms, computed on the host CPU."""
    import jax
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(seed)

        @jax.jit
        def draw(cells):
            ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(cells)
            return jax.vmap(
                lambda k: jax.random.uniform(k, (draws, 2), np.float32))(ks)
        return np.asarray(draw(np.arange(n_cells, dtype=np.int32)))


def sweep(config: dict, whatif: dict, seed: int, dtype=np.float64) -> dict:
    """The what-if's answer, plus, per cell, how many draws lie within
    `TIE_RTOL` of another core choice or histogram bin."""
    # every component's quantile is taken and the kind selects one: the
    # others may overflow or divide by a point mass's zero shape
    with np.errstate(over="ignore", divide="ignore", invalid="ignore"):
        return _sweep(config, whatif, seed, np.dtype(dtype))


def _sweep(config, whatif, seed, dt):
    def r(x):
        return np.asarray(x).astype(dt)

    def fn(f, x):
        """A transcendental, taken in float64 on the rounded input and
        rounded back to `dtype`."""
        return r(f(np.asarray(x, np.float64)))

    n_hist, n_pareto = whatif["n_hist"], whatif["n_pareto"]
    dists = [dist_comps(d) for d in whatif["dists"]]
    freqs = np.asarray(whatif["execs_per_day"], np.float64)
    intens = np.asarray(whatif["intensities"], np.float64)
    vols = np.asarray(whatif["volumes"], np.float64)
    emb, kwh = tables(config, whatif)
    T, W, C = kwh.shape
    D, F, I, V = len(dists), len(freqs), len(intens), len(vols)
    draws = whatif["draws"]
    n_cells = D * F * I * V * W * T

    # histogram and Pareto bin geometry (float64, from the anchors)
    life_max = max(support_max(c) for c in dists)
    tmax = float(emb.max() + kwh.max() * intens.max() * (life_max / DAY_S)
                 * freqs.max())
    hist_lo = math.log10(float(emb.min()))
    hist_inv = n_hist / max(math.log10(tmax) - hist_lo, 1e-9)
    par_lo = math.log10(float(emb.min()))
    par_inv = n_pareto / max(math.log10(float(emb.max())) - par_lo, 1e-9)

    # cell axes, slowest to fastest: dist, freq, intensity, volume,
    # workload, timing
    idx = np.indices((D, F, I, V, W, T)).reshape(6, -1)
    di, fi, ii, vi, wi, ti = idx
    u32 = uniforms(seed, n_cells, draws)
    uc = np.clip(u32[..., 0], np.float32(1e-6), np.float32(1 - 1e-6))
    K = max(len(c) for c in dists)
    kind = np.zeros((D, K), np.int32)
    p1 = np.ones((D, K))
    p2 = np.ones((D, K))
    cum = np.ones((D, K))
    for d, comps in enumerate(dists):
        for k, (kd, a, b, _) in enumerate(comps):
            kind[d, k], p1[d, k], p2[d, k] = kd, a, b
        cum[d, :len(comps)] = np.cumsum([c[3] for c in comps])
    comp = (u32[..., 1][..., None] >= cum[di, None, :max(K - 1, 1)]
            ).sum(-1) if K > 1 else np.zeros(uc.shape, np.int64)
    k = kind[di[:, None], comp]
    a = r(p1[di[:, None], comp])
    b = r(p2[di[:, None], comp])
    # the uniforms are data and stay float32; the arithmetic on them is
    # the reference's and runs in `dtype`
    logn = fn(np.exp, r(a + r(b * fn(ndtri, uc))))
    weib = r(a * fn(np.exp, r(fn(np.log, fn(lambda x: -np.log1p(-x), uc))
                              * r(r(1.0) / b))))
    life = np.where(k == POINT, a, np.where(k == LOGNORMAL, logn, weib))
    life_days = r(life / r(DAY_S))

    embc = r(emb[wi])                                     # (cells, C)
    kwhc = r(kwh[ti, wi])
    inten = r(intens[ii])[:, None]
    freq = r(freqs[fi])[:, None]
    ops = np.stack([r(r(r(kwhc[:, c:c + 1] * inten) * life_days) * freq)
                    for c in range(C)], -1)               # (cells, N, C)
    tots = r(embc[:, None, :] + ops)
    best = np.argmin(tots, axis=-1)                       # first-min ties
    best_total = np.take_along_axis(tots, best[..., None], -1)[..., 0]
    best_op = np.take_along_axis(ops, best[..., None], -1)[..., 0]
    best_emb = np.take_along_axis(
        np.broadcast_to(embc[:, None, :], tots.shape), best[..., None],
        -1)[..., 0]
    srt = np.sort(tots.astype(np.float64), axis=-1)
    tie = (srt[..., 1] - srt[..., 0]) <= TIE_RTOL * srt[..., 0] \
        if C > 1 else np.zeros(best.shape, bool)

    by_draw = np.sort(best_total, axis=1)
    qidx = [min(draws - 1, max(0, math.ceil(q / 100 * draws) - 1))
            for q in PCTS]
    mean = r(best_total.sum(1, dtype=dt) / r(draws))
    out = {
        "mean": mean, "p50": by_draw[:, qidx[0]], "p90": by_draw[:, qidx[1]],
        "p99": by_draw[:, qidx[2]], "min": best_total.min(1),
        "max": best_total.max(1),
        "mean_emb": r(best_emb.sum(1, dtype=dt) / r(draws)),
        "mean_op": r(best_op.sum(1, dtype=dt) / r(draws)),
        "fleet_mean": r(mean * r(vols[vi])),
        "counts": np.stack([(best == c).sum(1) for c in range(C)], -1),
        "tie_draws": tie.sum(1),
    }
    pos = (np.log10(best_total.astype(np.float64)) - hist_lo) * hist_inv
    bins = np.clip(np.floor(np.nan_to_num(pos, nan=n_hist - 1)), 0,
                   n_hist - 1).astype(np.int64)
    out["hist"] = np.bincount(bins.ravel(), minlength=n_hist)
    # a total this close to an inner bin edge may fall on either side;
    # the end bins take everything beyond them
    near = np.round(pos)
    edge = (np.abs(pos - near) <= TIE_RTOL * hist_inv / math.log(10)) \
        & (near >= 1) & (near <= n_hist - 1)
    out["edge_scenarios"] = int((edge | tie).sum())

    # Pareto: per embodied bin, the least operational carbon among the
    # scenarios whose chosen core's embodied carbon falls in that bin
    ebin = np.clip(np.floor((np.log10(emb) - par_lo) * par_inv), 0,
                   n_pareto - 1).astype(np.int64)          # (W, C)
    sbin = ebin[wi[:, None], best]                         # (cells, N)
    par_op = np.full(n_pareto, np.inf)
    par_emb = np.full(n_pareto, np.inf)
    par_tie = np.zeros(n_pareto, bool)
    flat_bin, flat_op = sbin.ravel(), best_op.astype(np.float64).ravel()
    flat_emb, flat_tie = best_emb.astype(np.float64).ravel(), tie.ravel()
    for bn in np.unique(flat_bin):
        sel = np.nonzero(flat_bin == bn)[0]
        j = sel[np.argmin(flat_op[sel])]
        par_op[bn], par_emb[bn], par_tie[bn] = flat_op[j], flat_emb[j], \
            flat_tie[j]
    out["pareto_op"], out["pareto_emb"], out["pareto_tie"] = \
        par_op, par_emb, par_tie
    out["shape"] = (D, F, I, V, W, T)
    return out


def compare(got: dict, ref: dict) -> dict:
    """The numbers `correct` rests on, each lower is better.

    rel_err: the largest relative gap of any per-cell statistic or
      Pareto point from the reference (the embodied/operational split
      only in cells with no near-tie draw);
    count_excess: chosen-core draws that differ from the reference,
      beyond twice the draws whose choice is a near-tie;
    hist_excess: histogram counts that differ, beyond twice the
      scenarios that lie at a near-tie or a bin edge.
    """
    rel = 0.0
    # a near-tie draw may take either core: the totals agree, but the
    # embodied/operational split of the chosen one moves by a whole draw
    clear = ref["tie_draws"] == 0
    for f in FIELDS:
        g = np.asarray(got[f], np.float64).ravel()
        w = np.asarray(ref[f], np.float64).ravel()
        if not np.isfinite(g).all():
            return {"rel_err": math.inf, "count_excess": math.inf,
                    "hist_excess": math.inf}
        err = np.abs(g - w) / np.abs(w)
        if f in ("mean_emb", "mean_op"):
            err = err[clear]
        rel = max(rel, float(err.max(initial=0.0)))
    ok = np.isfinite(ref["pareto_op"]) & ~ref["pareto_tie"]
    for f in ("pareto_op", "pareto_emb"):
        g = np.asarray(got[f], np.float64)[ok]
        w = ref[f][ok]
        if not np.isfinite(g).all():
            return {"rel_err": math.inf, "count_excess": math.inf,
                    "hist_excess": math.inf}
        rel = max(rel, float((np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
                              ).max(initial=0.0)))
    gc = np.asarray(got["counts"]).reshape(ref["counts"].shape)
    diff = np.abs(gc - ref["counts"]).sum(-1)
    count_excess = int(np.maximum(diff - 2 * ref["tie_draws"], 0).sum())
    hdiff = int(np.abs(np.asarray(got["hist"]) - ref["hist"]).sum())
    hist_excess = max(0, hdiff - 2 * ref["edge_scenarios"])
    return {"rel_err": rel, "count_excess": count_excess,
            "hist_excess": hist_excess}
