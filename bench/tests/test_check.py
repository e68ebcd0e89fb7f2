"""The checker accepts true answers and refuses altered ones."""

import numpy as np
import pytest

from bench import check

TOL = 1e-3
COUNT = np.array([50.0, 40.0, 0.0, 30.0, 20.0])
TRUTH = np.array([10.0, 12.0, 0.0, 7.0, 15.0])
LIMITS = {"exact_gap": TOL, "ci_miss": 0, "stop_wrong": 0}


def answer(est=None, half=1.0, exact=False):
    est = TRUTH.copy() if est is None else np.asarray(est, float)
    return {"estimate": est, "lo": est - half, "hi": est + half,
            "exact": np.full(len(est), exact)}


def tpl(kind, **kw):
    return {"stop": dict(kind=kind, **kw)}


def judge(t, a):
    return check.judge(t, a, COUNT, TRUTH, TOL)


@pytest.mark.parametrize("t", [
    tpl("topk_separated", k=1, largest=True),
    tpl("topk_separated", k=2, largest=False),
    tpl("groups_ordered"),
    tpl("threshold_side", threshold=11.0),
    tpl("relative_width", eps=0.5),
])
def test_true_answers_pass(t):
    for a in (answer(half=0.4), answer(exact=True, half=0.0)):
        nums = judge(t, a)
        assert nums["ci_miss"] == 0 and nums["stop_wrong"] == 0
    assert judge(t, answer(exact=True, half=0.0))["exact_gap"] == 0.0


def test_perturbed_interval_is_a_miss():
    a = answer(half=0.4)
    a["lo"][1] += 0.5                        # the interval no longer holds 12
    nums = judge(tpl("topk_separated", k=1), a)
    assert nums["ci_miss"] == 1
    _, failed, correct = check.summarize([nums], LIMITS)
    assert failed == 1 and not correct


def test_non_finite_endpoint_is_a_miss():
    a = answer(half=0.4)
    a["hi"][0] = np.nan
    assert judge(tpl("groups_ordered"), a)["ci_miss"] == 1


def test_exact_view_off_the_truth_breaks_the_gap():
    est = TRUTH.copy()
    est[3] += 10 * TOL
    nums = judge(tpl("groups_ordered"), answer(est, half=0.0, exact=True))
    assert nums["exact_gap"] == pytest.approx(10 * TOL)
    assert not check.summarize([nums], LIMITS)[2]


def test_wrong_top_k_is_refused():
    est = TRUTH.copy()
    est[0] = 20.0                            # group 0 claimed the largest
    nums = judge(tpl("topk_separated", k=1, largest=True),
                 answer(est, half=30.0))
    assert nums["ci_miss"] == 0 and nums["stop_wrong"] == 1


def test_wrong_order_and_wrong_side_are_refused():
    est = TRUTH.copy()
    est[[0, 1]] = est[[1, 0]]
    assert judge(tpl("groups_ordered"), answer(est, half=5.0))[
        "stop_wrong"] == 1
    a = answer(half=0.4)
    a["lo"][0], a["hi"][0] = 11.5, 12.5      # claims group 0 above 11
    assert judge(tpl("threshold_side", threshold=11.0), a)["stop_wrong"] == 1
    undecided = answer(half=2.0)             # 10 +- 2 straddles 11
    assert judge(tpl("threshold_side", threshold=11.0), undecided)[
        "stop_wrong"] == 1


def test_relative_width_off_by_more_than_eps():
    est = TRUTH.copy()
    est[4] = 30.0
    nums = judge(tpl("relative_width", eps=0.1), answer(est, half=1.0))
    assert nums["stop_wrong"] == 1 and nums["ci_miss"] == 1
