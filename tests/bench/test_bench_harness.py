"""Every cell, configuration, mix and metric of ``BENCHMARK.json`` is
found by name; each mix drives its cells at a tiny size on the CPU and
ends in the result line the contract asks for; and the command refuses
to print a result without a TPU or without the program."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import drive
from bench import run as bench_run

SPEC = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_is_found():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        path = os.path.join(bench_run.ROOT, c["file"])
        cfg = bench_run.load_json(path)
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert cfg["guarantees"] and cfg["source"]
        assert callable(drive.plugin("generators", cfg["generator"]).versions)
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert len(w["why"]) <= 200
        traffic = bench_run.load_json(bench_run.BENCH, "traffic",
                                      w["traffic"] + ".json")
        assert callable(drive.plugin("ops", traffic["op"]).run)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and callable(bench_run.reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_the_contract_asks(cell):
    e2e = [m["name"] for m in bench_run.cell_metrics(SPEC, cell, False)]
    layer = bench_run.cell_metrics(SPEC, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"] for m in SPEC["end_to_end"]}[
        "setup_s"] == 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_on_cpu_and_ends_in_the_result_line(cell, tiny):
    result, run = tiny(cell)
    assert result["correct"] is True, result["checks"]
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in bench_run.cell_metrics(SPEC, cell, False)}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def test_traced_run_reports_per_layer_metrics_and_breakdown(tiny):
    cell = "sql_backup.ingest"
    result, run = tiny(cell, traced=True)
    assert result["correct"] is True
    layer = {m["name"] for m in bench_run.cell_metrics(SPEC, cell, True)}
    # no device plane and no kernel call on the CPU: those readers are
    # silent, never 0
    assert set(result["metrics"]) == layer - {"sim_topk_roofline"}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "bench.chunk" in dict(result["breakdown"]["idle_gaps"])


def command(cwd, env):
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", str(2**40), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = command(bench_run.ROOT, env)
    assert out.returncode == 2 and out.stdout == ""
    assert "needs 1 TPU" in out.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(bench_run.ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(bench_run.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = command(tmp_path, env)
    assert out.returncode != 0 and out.stdout == ""
