"""The four-chip cell's path on four CPU devices: the frame built from
``flights-606m-4chip``'s engine block (row-sharded over a ``(4,)`` mesh,
a merge every round) answers one cycle of ``suite-solo-4chip`` with every
interval holding the truth, and reads the same blocks in the same rounds
as the scan on one device (``bench/tests/sharded_cell_worker.py``, in a
subprocess: the device count is fixed when JAX starts)."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

ROWS = 400_000
SEED = 2**31 + 11
# the cell's own limit (bench/limits/flights-606m-4chip.suite-solo.json):
# the divided run folds with the chip's kernels (interpreted), whose exact
# views read at most 6.7e-5 here over three seeds
EXACT_GAP = 2.5e-4


@pytest.fixture(scope="module")
def answers():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/tests/sharded_cell_worker.py", str(ROWS),
         str(SEED)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_scan_is_divided_over_four_devices(answers):
    assert answers["shards"] == 4
    with open(ROOT / "bench" / "traffic" / "suite-solo-4chip.json") as f:
        templates = json.load(f)["templates"]
    assert set(answers["templates"]) == set(templates)


def test_every_answer_holds_the_truth(answers):
    gaps = [t["exact_gap"] for t in answers["templates"].values()]
    assert any(g == g for g in gaps)    # full scans give exact views
    for name, t in answers["templates"].items():
        assert t["ci_miss"] == 0, name
        assert t["stop_wrong"] == 0, name
        assert not t["exact_gap"] > EXACT_GAP, name


def test_same_blocks_and_rounds_as_one_device(answers):
    """Selection, the cursor and the stop test are replicated on every
    shard, so the divided scan takes the one-device scan's decisions."""
    for name, t in answers["templates"].items():
        assert (t["rounds"], t["blocks"]) == (t["one_rounds"],
                                              t["one_blocks"]), name
