"""A run with the timed path broken underneath comes out not correct.

Each test drives ``harness.run_cell`` on the CPU at a small size, past
the harness's look for a chip, with one fault planted in the program
through its public names (``repro.kernels.ops``, ``FastFrame.run``),
so that a change inside the program does not move the plant: a round
whose fold adds nothing (the state left unchanged), half of every
block's rows left out of the fold (the means taken over the rest), and
an answer altered where it is produced. The one-chip cells have no
exchange between chips to leave out.

On the CPU the engine folds with XLA's float32 scatter-add (not the
chip's kernels), whose exact views sit about 1e-3 minutes off the truth
at this size; the tests hold ``exact_gap`` to 1e-2, which a sound run
meets and every fault breaks by far.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

TEMPLATES = ("F-q2", "F-q7", "F-q8")
ROWS = 200_000
LIMITS = {"exact_gap": 1e-2, "ci_miss": 0, "stop_wrong": 0}


@pytest.fixture()
def run(x64, small_cell, monkeypatch):
    monkeypatch.setattr(harness, "peak_hbm_bytes_per_s", lambda kind: 819e9)

    def go():
        cell = small_cell("flights-151m.suite-solo", ROWS, TEMPLATES)
        cell.limits = dict(LIMITS)
        return harness.run_cell(cell, 2**31 + 11, 0.0, False,
                                time.perf_counter(), jax.devices()[0])

    return go


def _wrap_fold(monkeypatch, wrap):
    """Put ``wrap(fold, values, gids, mask, *args, **kw)`` in the place of
    the engine's per-round fold, ``repro.kernels.ops.grouped_sums``."""
    from repro.kernels import ops

    fold = ops.grouped_sums
    monkeypatch.setattr(ops, "grouped_sums",
                        lambda *a, **kw: wrap(fold, *a, **kw))


def test_sound_run_is_correct(run):
    out = run()
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == len(TEMPLATES)
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"answer_ms_p50", "answer_ms_p95",
                                   "answers_per_s", "setup_s"}


def test_round_leaving_the_state_unchanged(run, monkeypatch):
    def nothing(fold, *args, **kw):
        sums, vmin, vmax = fold(*args, **kw)
        return (jnp.zeros_like(sums), jnp.full_like(vmin, jnp.inf),
                jnp.full_like(vmax, -jnp.inf))

    _wrap_fold(monkeypatch, nothing)
    out = run()
    assert not out["correct"]
    assert out["checks"]["exact_gap"]["value"] > LIMITS["exact_gap"]


def test_half_of_the_rows_left_out(run, monkeypatch):
    def half(fold, values, gids, mask, *args, **kw):
        if mask is None:
            mask = jnp.ones_like(values, dtype=jnp.float32)
        return fold(values, gids, mask.reshape(-1).at[::2].set(0.0), *args,
                    **kw)

    _wrap_fold(monkeypatch, half)
    out = run()
    assert not out["correct"]
    assert out["checks"]["exact_gap"]["value"] > LIMITS["exact_gap"]


def test_answer_altered_where_produced(run, monkeypatch):
    from repro.aqp import FastFrame

    answer = FastFrame.run

    def altered(self, *args, **kw):
        res = answer(self, *args, **kw)
        i = int(np.argmax(res.count_seen))
        for f in ("estimate", "lo", "hi"):
            getattr(res, f)[i] += 1.0
        return res

    monkeypatch.setattr(FastFrame, "run", altered)
    out = run()
    assert not out["correct"]
    assert out["checks"]["ci_miss"]["value"] > 0
