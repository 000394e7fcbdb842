"""The harness finds a cell's parts by name: a configuration, traffic
mix or per-layer metric dropped in as a new file is found with no edit
to any file that is there. And the command refuses to run without a
TPU, or without the program beside it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import registry
from perfbench.tests import helpers

ROOT = registry.ROOT


@pytest.fixture
def tree(tmp_path):
    """A copy of BENCHMARK.json and perfbench/ to add files to."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(registry.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return tmp_path


def _add_entries(tree, **lists):
    path = tree / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for key, items in lists.items():
        bench[key].extend(items)
    path.write_text(json.dumps(bench))
    return bench


def test_new_configuration_and_traffic_are_found_by_name(tree):
    conf = dict(registry.config(registry.benchmark(), "paper3"),
                name="paper3_wide_budget", budget_per_request=1.9e-3)
    (tree / "perfbench/configs/paper3_wide_budget.json").write_text(
        json.dumps(conf))
    traffic = dict(registry.traffic("steady"))
    traffic["arrivals"] = {"process": "poisson", "rate_per_s": 1234}
    (tree / "perfbench/traffic/steady_1234.json").write_text(
        json.dumps(traffic))
    bench = _add_entries(
        tree,
        configs=[{"name": "paper3_wide_budget", "source": "test",
                  "file": "perfbench/configs/paper3_wide_budget.json",
                  "reduced": [], "why": "test"}],
        workloads=[{"name": "wide_1234", "config": "paper3_wide_budget",
                    "traffic": "steady_1234", "chips": 1, "why": "test"}])
    wl = registry.workload(bench, "wide_1234")
    assert registry.config(bench, wl["config"], root=str(tree))[
        "budget_per_request"] == 1.9e-3
    assert registry.traffic(wl["traffic"], here=str(tree / "perfbench"))[
        "arrivals"]["rate_per_s"] == 1234


def test_new_metric_reader_is_found_by_name(tree):
    (tree / "perfbench/metrics/rows_per_block_max.py").write_text(
        "def read(ctx):\n    return ctx.layer.get('rows_max')\n")
    bench = _add_entries(tree, per_layer=[{
        "name": "rows_per_block_max", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "admission",
        "moves": "route_p99_ms", "workloads": ["paper3_steady"]}])
    names = [m["name"] for m in registry.per_layer_of(bench, "paper3_steady")]
    assert "rows_per_block_max" in names
    mod = registry.metric("rows_per_block_max", here=str(tree / "perfbench"))

    class Ctx:
        layer = {"rows_max": 17}

    assert mod.read(Ctx()) == 17


@pytest.mark.parametrize("test_cells", [False, True])
def test_every_declared_part_exists(test_cells):
    """Every part BENCHMARK.json names exists (and, with ``test_cells``,
    every part of the cells the tests drive, and every metric reader)."""
    bench = helpers.bench() if test_cells else registry.benchmark()
    for wl in bench["workloads"]:
        registry.config(bench, wl["config"])
        traffic = registry.traffic(wl["traffic"])
        registry.entry(traffic["entry"])
        assert os.path.exists(os.path.join(
            registry.HERE, "limits", wl["name"] + ".json"))
    for m in bench["per_layer"]:
        assert callable(registry.metric(m["name"]).read)
        for w in m["workloads"]:
            e2e = {e["name"] for e in registry.end_to_end_of(bench, w)}
            assert m["moves"] in e2e
    if test_cells:
        readers = [f[:-3] for f in os.listdir(os.path.join(
            registry.HERE, "metrics")) if f.endswith(".py")]
        assert len(readers) >= 12
        assert all(callable(registry.metric(n).read) for n in readers)


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         registry.benchmark()["workloads"][0]["name"],
         "--seed", "2147483653", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_device():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "needs a TPU" in p.stderr and "cpu" in p.stderr


def test_run_refuses_without_the_program(tree):
    p = _run(tree, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "{" not in p.stdout
