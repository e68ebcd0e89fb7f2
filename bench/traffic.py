"""The general traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix is data::

    {"client": "frame_run", "trace": ["F-q2", "F-q8"],
     "pick": {"kind": "each"},
     "arrivals": {"kind": "closed"},
     "templates": {"<name>": {"agg": "avg", "column": "dep_delay",
                              "filters": [["origin", "eq", 0]],
                              "group_by": ["airline"],
                              "stop": {"kind": "topk_separated", "k": 1},
                              "bounder": "bernstein", "rangetrim": true,
                              "delta": 1e-15}, ...}}

* ``client`` names the module ``bench/clients/<client>.py`` that warms
  the program up and asks it the questions (see ``bench/harness.py``).
* Questions come in cycles. ``pick`` ``each`` (the default) asks every
  template once per cycle, in an order drawn from the seed;
  ``{"kind": "weighted", "weights": {"<name>": w, ...}, "per_cycle": n}``
  draws ``n`` templates by weight.
* Each question scans from a block drawn from the seed (the paper's
  random scan start, §5.2).
* A filter's value is a number, or is drawn afresh for each question:
  ``{"zipf": s, "n": n}`` gives code ``k`` of ``0..n-1`` with weight
  ``(k + 1) ** -s``; ``{"uniform": [lo, hi], "step": d}`` gives one of
  ``lo, lo + d, ..., hi``.
* ``arrivals`` ``closed`` (the default) has no due times: the next
  question goes out when the last answer is in. ``{"kind": "poisson",
  "rate_per_s": r}`` spaces a cycle's questions by exponential gaps;
  ``{"kind": "burst"}`` makes them all due at the cycle's start.
* ``trace`` names the templates whose first question a traced run
  profiles: they go first in the window's first cycle. The profiler's
  cost grows with the device operations it records (about 135 a round
  on a TPU v5e), and its buffer dropped events between 2.6 and 4.4
  million, so the list is chosen to stay well under that.

The same seed gives the same questions in the same order, whatever the
timing. Templates become the program's query objects through its public
``AggQuery``, ``Filter`` and stopping-condition types.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from typing import Optional

import numpy as np

from bench.reference import group_cols

PICKS = ("each", "weighted")
ARRIVALS = ("closed", "poisson", "burst")


@dataclasses.dataclass(frozen=True)
class Request:
    """One question of a cycle."""

    template: str
    spec: dict              # the template with every drawn value filled in
    start: int              # scan start block
    due_s: Optional[float]  # seconds after the cycle's start; None: closed
    traced: bool = False


def _drawn(value) -> bool:
    return isinstance(value, dict)


def validate(mix: dict) -> None:
    templates = mix.get("templates")
    if not templates:
        raise ValueError("a traffic mix needs at least one template")
    client = mix.get("client", "")
    if not (client.isidentifier() and importlib.util.find_spec(
            f"bench.clients.{client}") is not None):
        raise ValueError(f"no client bench/clients/{client}.py")
    pick = mix.get("pick", {"kind": "each"})
    if pick["kind"] not in PICKS:
        raise ValueError(f"unknown pick {pick['kind']!r}")
    if pick["kind"] == "weighted" and (
            set(pick["weights"]) - set(templates) or pick["per_cycle"] < 1):
        raise ValueError("weighted pick: unknown template or per_cycle < 1")
    if mix.get("arrivals", {"kind": "closed"})["kind"] not in ARRIVALS:
        raise ValueError(f"unknown arrivals {mix['arrivals']['kind']!r}")
    if not mix.get("trace") or set(mix["trace"]) - set(templates):
        raise ValueError("trace has to name templates of the mix")
    for name, tpl in templates.items():
        for _, _, value in tpl.get("filters", ()):
            if _drawn(value) and not ({"zipf", "n"} == set(value) or
                                      {"uniform", "step"} == set(value)):
                raise ValueError(f"{name}: unknown filter draw {value!r}")


def build_query(spec: dict):
    """The program's ``AggQuery`` for one question's template."""
    from repro.aqp.query import AggQuery, Filter
    from repro.core import optstop

    stops = {"relative_width": optstop.RelativeWidth,
             "threshold_side": optstop.ThresholdSide,
             "topk_separated": optstop.TopKSeparated,
             "groups_ordered": optstop.GroupsOrdered}
    stop = dict(spec["stop"])
    kind = stop.pop("kind")
    g = group_cols(spec)
    return AggQuery(
        agg=spec["agg"], column=spec["column"],
        filters=tuple(Filter(c, op, v) for c, op, v in spec.get("filters",
                                                                ())),
        group_by=(g[0] if len(g) == 1 else g) if g else None,
        stop=stops[kind](**stop), bounder=spec["bounder"],
        rangetrim=spec["rangetrim"], delta=spec["delta"])


def _draw(value, rng: np.random.Generator):
    if "zipf" in value:
        w = np.arange(1, value["n"] + 1, dtype=np.float64) ** -value["zipf"]
        return int(rng.choice(value["n"], p=w / w.sum()))
    lo, hi = value["uniform"]
    steps = int(round((hi - lo) / value["step"]))
    return lo + value["step"] * int(rng.integers(steps + 1))


def _spec(tpl: dict, rng: np.random.Generator) -> dict:
    if not any(_drawn(v) for _, _, v in tpl.get("filters", ())):
        return tpl
    return dict(tpl, filters=[[c, op, _draw(v, rng) if _drawn(v) else v]
                              for c, op, v in tpl["filters"]])


def cycle(mix: dict, seed: int, index: int, n_blocks: int,
          traced: bool = False) -> list:
    """The requests of cycle ``index``. With ``traced`` the first request
    of each template that ``mix["trace"]`` names is marked and moved to
    the front, in the cycle's order."""
    rng = np.random.default_rng([seed, index])
    names = sorted(mix["templates"])
    pick = mix.get("pick", {"kind": "each"})
    if pick["kind"] == "each":
        order = rng.permutation(len(names))
    else:
        w = np.array([pick["weights"].get(n, 0.0) for n in names])
        order = rng.choice(len(names), size=pick["per_cycle"], p=w / w.sum())
    starts = rng.integers(n_blocks, size=len(order))
    arrivals = mix.get("arrivals", {"kind": "closed"})
    due = [None] * len(order)
    if arrivals["kind"] == "poisson":
        due = np.cumsum(rng.exponential(1.0 / arrivals["rate_per_s"],
                                        size=len(order))).tolist()
    elif arrivals["kind"] == "burst":
        due = [0.0] * len(order)
    reqs = [Request(names[i], _spec(mix["templates"][names[i]], rng), int(s),
                    d) for i, s, d in zip(order, starts, due)]
    if not traced:
        return reqs
    first = {}
    for k, r in enumerate(reqs):
        if r.template in mix["trace"]:
            first.setdefault(r.template, k)
    marked = sorted(first.values())
    return ([dataclasses.replace(reqs[k], traced=True) for k in marked]
            + [r for k, r in enumerate(reqs) if k not in marked])
