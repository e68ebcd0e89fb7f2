"""Judge the engine's answers against the plain reference.

Three numbers are compared, each with its limit from
``bench/limits/<workload>.json``:

* ``exact_gap``: the widest gap, in the value column's units, between a
  fully covered (``exact``) view's estimate and the truth, over every
  answer. It covers the fold, the gather and the float64 merge.
* ``ci_miss``: how many views' intervals miss the truth by more than the
  ``exact_gap`` limit (a non-finite endpoint counts as a miss). It covers
  the bounders, RangeTrim and DKW.
* ``stop_wrong``: how many answers give a wrong answer to what the query
  asked: a top-k set, the side of a HAVING threshold, the order of the
  groups, or an estimate outside the relative accuracy it stopped at. It
  covers OptStop.

An answer is a mapping (or object) with ``estimate``, ``lo``, ``hi`` and
``exact`` arrays indexed by group code; a template is the traffic file's
data, so nothing here reads the program.
"""

from __future__ import annotations

import math

import numpy as np

NUMBERS = ("exact_gap", "ci_miss", "stop_wrong")


def _stop_wrong(stop: dict, est, lo, hi, exact, truth, tol) -> bool:
    kind = stop["kind"]
    if kind == "topk_separated":
        k, largest = stop["k"], stop.get("largest", True)
        sign = -1.0 if largest else 1.0
        got = np.argsort(sign * est, kind="stable")[:k]
        want = np.argsort(sign * truth, kind="stable")[:k]
        return set(got.tolist()) != set(want.tolist())
    if kind == "groups_ordered":
        return not np.array_equal(np.argsort(est, kind="stable"),
                                  np.argsort(truth, kind="stable"))
    if kind == "threshold_side":
        t = stop["threshold"]
        above = np.where(exact, est > t, lo > t)
        below = np.where(exact, est < t, hi < t)
        wrong = (above & (truth <= t - tol)) | (below & (truth >= t + tol))
        return bool(wrong.any() or (~above & ~below & ~exact).any())
    if kind == "relative_width":
        bound = stop["eps"] * np.maximum(np.abs(lo), np.abs(hi)) + tol
        return bool((np.abs(est - truth) > bound).any())
    raise ValueError(f"unknown stopping condition {kind!r}")


def judge(tpl: dict, answer, truth_count, truth_mean, tol: float) -> dict:
    """Numbers of one answer: ``exact_gap`` (NaN when no view is exact),
    ``ci_miss`` and ``stop_wrong`` (0 or 1), over the views that hold at
    least one row."""
    get = (answer.get if isinstance(answer, dict)
           else lambda k: getattr(answer, k))
    has = truth_count > 0
    est = np.asarray(get("estimate"), np.float64)[has]
    lo = np.asarray(get("lo"), np.float64)[has]
    hi = np.asarray(get("hi"), np.float64)[has]
    exact = np.asarray(get("exact"), bool)[has]
    truth = truth_mean[has]
    gaps = np.abs(est - truth)[exact]
    gap = float(gaps.max()) if gaps.size else math.nan
    if not np.isfinite(gap) and gaps.size:
        gap = math.inf
    finite = np.isfinite(lo) & np.isfinite(hi)
    miss = ~finite | (truth < lo - tol) | (truth > hi + tol)
    wrong = _stop_wrong(tpl["stop"], est, lo, hi, exact, truth, tol)
    return {"exact_gap": gap, "ci_miss": int(miss.sum()),
            "stop_wrong": int(wrong)}


def summarize(per_answer: list, limits: dict) -> tuple:
    """``(numbers, failed, correct)`` over all answers: the widest
    ``exact_gap``, the summed counts, the answers that broke a limit, and
    whether every number is within its limit."""
    gaps = [a["exact_gap"] for a in per_answer
            if not math.isnan(a["exact_gap"])]
    numbers = {"exact_gap": max(gaps) if gaps else 0.0,
               "ci_miss": sum(a["ci_miss"] for a in per_answer),
               "stop_wrong": sum(a["stop_wrong"] for a in per_answer)}
    failed = sum(
        1 for a in per_answer
        if (not math.isnan(a["exact_gap"])
            and not a["exact_gap"] <= limits["exact_gap"])
        or a["ci_miss"] > limits["ci_miss"]
        or a["stop_wrong"] > limits["stop_wrong"])
    correct = bool(per_answer) and all(
        numbers[k] <= limits[k] for k in NUMBERS)
    return numbers, failed, correct
