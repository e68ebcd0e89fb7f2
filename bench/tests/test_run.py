"""``bench/run.py`` refuses to run without a TPU, and BENCHMARK.json names
only files, clients and readers that exist."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import traffic
from bench.tests.conftest import ROOT, benchmark_spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "flights-151m.suite-solo", "--seed", str(2**31 + 9), "--seconds",
         "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_and_prints_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_exit_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_every_name_resolves_to_a_file():
    spec = benchmark_spec()
    for cfg in spec["configs"]:
        assert NAME.match(cfg["name"])
        assert (ROOT / cfg["file"]).is_file()
        assert cfg["file"].startswith("bench/")
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        mix = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        traffic.validate(json.loads(mix.read_text()))
        assert (ROOT / "bench" / "clients"
                / f"{json.loads(mix.read_text())['client']}.py").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      benchmark_spec()["workloads"]])
def test_cells_load(workload):
    from bench import harness

    cell = harness.load_cell(ROOT, workload)
    assert cell.per_layer and len(cell.end_to_end) >= 2
    assert set(cell.limits) == {"exact_gap", "ci_miss", "stop_wrong"}
