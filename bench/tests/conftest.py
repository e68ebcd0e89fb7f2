"""Fixtures of the benchmark's CPU tests: 64-bit JAX types (the engine's
device loop needs them) and small cells built from the real files."""

import copy
import json
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


@pytest.fixture()
def small_cell():
    """``make(workload, rows, templates=None)``: the workload's cell with
    the table cut to ``rows`` and the mix to ``templates``."""
    from bench import harness

    def make(workload, rows, templates=None):
        cell = copy.deepcopy(harness.load_cell(ROOT, workload))
        cell.config["rows"] = rows
        if templates is not None:
            cell.mix["templates"] = {k: cell.mix["templates"][k]
                                     for k in templates}
        return cell

    return make


def benchmark_spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())
