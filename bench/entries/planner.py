"""Entry `planner`: one request is one carbon what-if through
`repro.core.sweep.run_sweep`.

The what-if is the traffic file's scenario space (lifetime
distributions, task frequencies, grid intensities, fleet volumes and
timing modes, times Monte Carlo draws) over the configuration's
workloads, priced on its cores from its recorded profiles. The same
what-if is asked again and again; the program's defaults choose the path
and the tiling. Its Monte Carlo seed is the traffic file's `spec_seed`,
not the run's: the program compiles its sweep step for each what-if it
is given, so a what-if seeded per run would make set-up depend on which
seeds the compile cache has already seen.

Correctness: once the window has closed, the first and last answers and
four drawn from the run's seed are compared with the float64 planner in
`reference/planner.py` over every cell (see `planner.compare`).
"""
from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

from reference import planner as ref_planner

# The what-if asks for float32. On a v5e its answers lie up to 1.4e-4
# from the float64 reference over 18 seeds (the chip's float32 log1p and
# normal quantile, in the lifetime draws); the bfloat16 control reads
# 1.66 on every seed. The limit sits between, with more room above the
# sound readings (PERF.md section 4).
REL_ERR_LIMIT = 2e-2


def build_spec(config: dict, whatif: dict, seed: int):
    from repro.core.carbon import DeviceProfile
    from repro.core.sweep import LifetimeDist, SweepSpec
    from repro.flexibits.cycles import CORES

    def dist(d):
        parts = []
        for c in d["comps"]:
            if c["kind"] == "point":
                one = LifetimeDist.point(c["days"] * ref_planner.DAY_S)
            elif c["kind"] == "lognormal":
                one = LifetimeDist.lognormal(
                    c["median_days"] * ref_planner.DAY_S, c["sigma"])
            else:
                one = LifetimeDist.weibull(
                    c["scale_days"] * ref_planner.DAY_S, c["shape"])
            parts.append((one, c.get("weight", 1.0)))
        return parts[0][0] if len(parts) == 1 else \
            LifetimeDist.mixture(parts)

    plan = config["planner"]
    keys = tuple(plan["profiles"])
    cores = tuple(CORES[name] for name in config["cores"])
    for core in cores:
        c = config["cores"][core.name]
        got = {"width": core.width, "area_mm2": core.area_mm2,
               "power_mw": core.power_mw, "a": core.a, "b": core.b}
        if got != c:
            raise RuntimeError(f"core {core.name} differs from Table 7")
    profiles = tuple(DeviceProfile(
        n_one_stage=p["n_one_stage"], n_two_stage=p["n_two_stage"],
        vm_kb=p["vm_kb"], nvm_kb=p["nvm_kb"], events=tuple(p["events"]))
        for p in (plan["profiles"][k] for k in keys))
    wcet = tuple(tuple(plan["wcet_cycles"][k][c.name] for c in cores)
                 for k in keys)
    return SweepSpec(
        workloads=keys, profiles=profiles,
        dists=tuple(dist(d) for d in whatif["dists"]),
        execs_per_day=tuple(float(f) for f in whatif["execs_per_day"]),
        intensities=tuple(float(i) for i in whatif["intensities"]),
        volumes=tuple(float(v) for v in whatif["volumes"]), cores=cores,
        timing=tuple(whatif["timing"]), draws=whatif["draws"], seed=seed,
        clock_hz=config["clock_hz"], wcet_cycles=wcet)


def answer_of(res) -> dict:
    out = {f: np.asarray(getattr(res, f)) for f in ref_planner.FIELDS}
    out["counts"] = np.asarray(res.counts)
    out["hist"] = np.asarray(res.hist)
    out["pareto_op"] = np.asarray(res.pareto["op"])
    out["pareto_emb"] = np.asarray(res.pareto["emb"])
    return out


class Session:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.core.sweep import run_sweep
        if len(devices) != 1:
            raise ValueError("the planner runs on one chip")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spec_seed = traffic["spec_seed"]
        self.spec = build_spec(config, traffic, self.spec_seed)
        self._run = run_sweep

    def _ask(self):
        return self._run(self.spec, dtype=np.dtype(self.traffic["dtype"]),
                         n_hist=self.traffic["n_hist"],
                         n_pareto=self.traffic["n_pareto"])

    def warmup(self) -> None:
        self._ask()

    def request(self, i: int) -> dict:
        res = self._ask()
        return {"n_scenarios": res.n_scenarios, "answer": answer_of(res)}

    @staticmethod
    def counters(whatifs: List[dict]) -> dict:
        return {"scenarios": sum(w["n_scenarios"] for w in whatifs)}

    @staticmethod
    def end_to_end(whatifs: List[dict], latencies: List[float],
                   window_s: float) -> dict:
        import statistics
        lat = sorted(latencies)
        p95 = statistics.quantiles(lat, n=20, method="inclusive")[18] \
            if len(lat) > 1 else lat[0]
        return {"scenarios_per_s":
                sum(w["n_scenarios"] for w in whatifs) / window_s,
                "whatif_p95_ms": p95 * 1e3}

    def release(self) -> None:
        self.spec = None

    def sample(self, whatifs: List[dict]) -> List[int]:
        rng = np.random.default_rng([self.seed, 0x5A17])
        n = len(whatifs)
        drawn = rng.choice(n, size=min(4, n), replace=False)
        return sorted({0, n - 1, *(int(x) for x in drawn)})

    def reference(self, dtype=np.float64) -> dict:
        return ref_planner.sweep(self.config, self.traffic, self.spec_seed,
                                 dtype=dtype)

    def control(self, whatifs: List[dict]) -> Callable:
        """The control's answers: the reference in bfloat16."""
        import ml_dtypes
        low = self.reference(dtype=ml_dtypes.bfloat16)
        return lambda i: low

    def check(self, whatifs: List[dict],
              answer: Optional[Callable] = None) -> tuple:
        want = self.reference()
        worst = {"rel_err": 0.0, "count_excess": 0, "hist_excess": 0}
        failed = 0
        picks = self.sample(whatifs)
        for i in picks:
            got = answer(i) if answer else whatifs[i]["answer"]
            nums = ref_planner.compare(got, want)
            bad = nums["rel_err"] > REL_ERR_LIMIT or nums["count_excess"] \
                or nums["hist_excess"]
            failed += bool(bad)
            worst = {k: max(worst[k], nums[k]) for k in worst}
        checks = {"rel_err": (worst["rel_err"], REL_ERR_LIMIT),
                  "count_excess": (worst["count_excess"], 0),
                  "hist_excess": (worst["hist_excess"], 0)}
        return checks, failed, len(picks)
