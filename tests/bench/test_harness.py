"""The benchmark harness on the CPU: discovery by name, the contract's
character rules, metric wiring, refusal without a TPU or without the
program, and every entry and metric driven at a tiny size. No timing is
judged here."""
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from bench_helpers import BENCH, ROOT, load_harness, tiny

bench_run = load_harness()
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def copy_tree(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))


def add_cell(root, **cell):
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["workloads"].append(cell)
    json.dump(spec, open(path, "w"))


NEW_ENTRY = '''
class Session:
    def __init__(self, config, traffic, seed, devices):
        self.n = traffic["n"]
'''
NEW_METRIC = '''
LAYER = "echo"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(trace, counters):
    return None
'''


@pytest.mark.parametrize("kind", ["config", "traffic", "entry", "metric"])
def test_a_new_file_is_found_by_name(tmp_path, kind):
    root = str(tmp_path)
    copy_tree(root)
    b = os.path.join(root, "bench")
    config, traffic = "fs-patch", "jobs-8192pg"
    if kind == "config":
        config = "fs-patch-copy"
        shutil.copy(os.path.join(b, "configs", "fs-patch.json"),
                    os.path.join(b, "configs", config + ".json"))
    if kind in ("traffic", "entry"):
        traffic = "new-mix"
        mix = {"entry": "fleet", "items_per_group": 3}
        if kind == "entry":
            mix = {"entry": "echo", "n": 7}
            with open(os.path.join(b, "entries", "echo.py"), "w") as f:
                f.write(NEW_ENTRY)
        json.dump(mix, open(os.path.join(b, "traffic", traffic + ".json"),
                            "w"))
    add_cell(root, name="new-cell", config=config, traffic=traffic, chips=1,
             why="a cell added as files only")
    if kind == "metric":
        with open(os.path.join(b, "metrics", "echo_share.py"), "w") as f:
            f.write(NEW_METRIC)
        path = os.path.join(root, "BENCHMARK.json")
        spec = json.load(open(path))
        spec["per_layer"].append({
            "name": "echo_share", "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "echo",
            "moves": "setup_s", "workloads": ["new-cell"]})
        json.dump(spec, open(path, "w"))
    got = bench_run.resolve("new-cell", root=root)
    assert got["cell"]["config"] == config
    assert got["config"]["name"] == "fs-patch"
    if kind == "traffic":
        assert got["traffic"]["items_per_group"] == 3
    if kind == "entry":
        assert got["entry"].Session({}, got["traffic"], 0, []).n == 7
    if kind == "metric":
        assert got["readers"]["echo_share"].LAYER == "echo"
        assert [m["name"] for m in got["per_layer"]] == ["echo_share"]


def test_names_units_and_keys_obey_the_rules():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and ONE_LINE.match(c["source"])
        assert ONE_LINE.match(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] \
            == c["name"]
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert ONE_LINE.match(w["why"])
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    cells = [w["name"] for w in SPEC["workloads"]]
    assert len(set(cells)) == len(cells)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= set(cells) if "workloads" in m \
            else True
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == LAYER_KEYS
        assert ONE_LINE.match(m["layer"])


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_each_layer_metric_moves_one_metric_its_cells_report(metric):
    m = next(x for x in SPEC["per_layer"] if x["name"] == metric)
    moved = next(x for x in SPEC["end_to_end"] if x["name"] == m["moves"])
    cells = [w["name"] for w in SPEC["workloads"]]
    for cell in m.get("workloads", cells):
        assert cell in moved.get("workloads", cells)
    reader = bench_run.load_module(
        os.path.join(BENCH, "metrics", metric + ".py"), "t_" + metric)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == \
        (m["layer"], m["unit"], m["source"], m["moves"])


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in SPEC["workloads"]:
        spec = bench_run.resolve(w["name"])
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec["per_layer"]


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fs-fleet", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    copy_tree(str(tmp_path))
    shutil.copytree(os.path.join(ROOT, "tests", "bench"),
                    os.path.join(str(tmp_path), "tests", "bench"))
    p = _run_cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell,trace", [
    ("fs-fleet", False), ("fs-fleet", True),
    ("table2-fleet", False),
    ("table2-planner", False), ("table2-planner", True),
])
def test_each_entry_and_metric_runs_at_a_tiny_size(cell, trace):
    spec = tiny(bench_run.resolve(cell))
    res = bench_run.run(spec, seed=2**33 + 3, seconds=0.2, trace=trace,
                        devices=jax.devices()[:1])
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    want = spec["per_layer"] if trace else spec["end_to_end"]
    if trace:
        # the CPU has no device plane: only counter metrics can be read
        assert set(res["metrics"]) <= {m["name"] for m in want}
        assert "busy_s" in res["device"] and "window_s" in res["device"]
        if spec["traffic"]["entry"] == "fleet":
            assert 0 < res["metrics"]["lane_occupancy"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {m["name"] for m in want}
        assert all(v["value"] > 0 for v in res["metrics"].values())


def test_the_planner_asks_the_same_what_if_under_every_seed():
    """Set-up compiles the sweep step for the what-if it is given, so the
    what-if may not follow the run's seed."""
    spec = tiny(bench_run.resolve("table2-planner"))
    a, b = (spec["entry"].Session(spec["config"], spec["traffic"], seed,
                                  jax.devices()[:1])
            for seed in (1, 2**33 + 7))
    assert a.spec == b.spec
    assert a.spec.seed == spec["traffic"]["spec_seed"]


def test_a_traced_run_compiles_apart(monkeypatch, tmp_path):
    """Traced runs compile without per-operation trace points, into a
    cache of their own, so no program crosses between the two kinds."""
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_foo=1")
    bench_run.trace_programs_whole()
    assert os.environ["LIBTPU_INIT_ARGS"] == \
        "--xla_foo=1 --xla_enable_hlo_trace=false"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        plain = bench_run.enable_compile_cache(str(tmp_path))
        traced = bench_run.enable_compile_cache(str(tmp_path), traced=True)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    assert plain == os.path.join(str(tmp_path), ".jax_cache")
    assert traced == os.path.join(plain, "traced")


MESH_RUN = """
import json, sys
sys.path.insert(0, {tests!r})
import jax
from bench_helpers import load_harness, tiny
bench_run = load_harness()
sys.path.insert(0, bench_run.program_root())
spec = tiny(bench_run.resolve("table2-fleet"))
res = bench_run.run(spec, seed=2**33 + 5, seconds=0.1, trace=False,
                    devices=jax.devices()[:4])
print(json.dumps(res))
"""


def test_the_fleet_entry_runs_shard_local_over_four_devices():
    """The entry's mesh path, on four virtual CPU devices: the same
    checks hold when a job is split over a ("fleet",) mesh."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_RUN.format(tests=os.path.join(ROOT, "tests", "bench"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
