"""On-chip smoke of the AQP engine over synthetic FLIGHTS.

One chip (the default): generate FLIGHTS from ``--seed`` at ``--rows``,
build the scramble, and answer the paper's F-q1..F-q9 suite (plus F-q9
under the Anderson/DKW bounder, which drives the histogram fold) through
each entry point a user calls: ``FastFrame.run``,
``FrameServer.run_batch`` and ``QueryScheduler``. Every interval is
checked against an exact numpy reference over the same rows.

``--chips 4``: only the divided scan -- the same frame sharded over a
``(4,)`` mesh at ``merge_every`` 1 and 4 -- and the single-device device
loop it is compared with, in the same process.

The script needs a TPU: without one it exits non-zero before any work.
Its last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every check passed. Times printed on earlier lines are
smoke timings (compiles included), not benchmark numbers.

    python chip_smoke.py [--rows N] [--seed S]
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import operator
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.aqp import EngineConfig, FastFrame, build_scramble  # noqa: E402
from repro.aqp import flights_queries as fq  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.optstop import GroupsOrdered, TopKSeparated  # noqa: E402
from repro.data import flights  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import FrameServer, QueryScheduler, WallClock  # noqa: E402

PAPER_ROWS = 606_000_000   # FLIGHTS, paper §5.1 / Table 3
BLOCK_ROWS = 1024
# The fold sums rows in f32 before the f64 merge: answers are compared
# with the f64 numpy truth within this share of the column's span.
F32_TOL = 1e-4
# sharded-vs-single CI endpoints: the f32 reorder bound of
# EngineConfig.shard_rows (tests/helpers/sharded_scenarios.CI_RTOL)
SHARD_CI_RTOL = 1e-3
# scan decisions the divided scan must reproduce exactly
SCAN_FIELDS = ("group_codes", "count_seen", "nonempty", "exact", "tainted",
               "rows_covered", "blocks_fetched", "blocks_skipped_active",
               "blocks_skipped_static", "bitmap_probes", "rounds",
               "stopped_early")
RECOVERY_EVENTS = {"fault", "retry", "degrade", "ladder-exhausted",
                   "finish-partial", "quarantine"}
SCHEDULER_QUERIES = ("F-q2", "F-q5", "F-q7", "F-q9")
SHARDED_QUERIES = ("F-q2", "F-q6", "F-q9/dkw")
COMPARE = {"eq": operator.eq, "ne": operator.ne, "gt": operator.gt,
           "ge": operator.ge, "lt": operator.lt, "le": operator.le}


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def require_tpu(chips: int):
    """The first device, and the kernel backend the engine resolves to;
    fails unless that is a TPU running compiled Pallas kernels."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    impl = kops.resolve_impl(None)
    if impl != "pallas":
        fail(f"kernel backend resolves to {impl!r}, not 'pallas'")
    if len(devices) < chips:
        fail(f"--chips {chips} but JAX sees {len(devices)} devices")
    return devices[0], impl


class CompileClock:
    """Sums the XLA backend compile time that JAX reports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


class Reference:
    """Exact per-group AVG over the generated rows, computed with numpy
    alone: filters applied, then ``np.bincount`` per composite group."""

    def __init__(self, columns):
        self.cols = columns
        self.n = len(next(iter(columns.values())))
        self._codes = {}
        self._avgs = {}

    def codes(self, group_cols):
        if group_cols not in self._codes:
            code = np.zeros(self.n, np.int64)
            card = 1
            for c in group_cols:
                cc = int(self.cols[c].max()) + 1
                code = code * cc + self.cols[c]
                card *= cc
            self._codes[group_cols] = (code.astype(np.int32), card)
        return self._codes[group_cols]

    def avg(self, q):
        """``(count, mean)`` per group code of ``q``'s view, computed once
        per (filters, group-by, column)."""
        key = (tuple(f.key() for f in q.filters), q.group_cols, q.column)
        if key not in self._avgs:
            self._avgs[key] = self._avg(q)
        return self._avgs[key]

    def _avg(self, q):
        keep = np.ones(self.n, bool)
        for f in q.filters:
            keep &= COMPARE[f.op](self.cols[f.column], f.value)
        code, card = self.codes(q.group_cols)
        code = code[keep]
        count = np.bincount(code, minlength=card)
        total = np.bincount(code, weights=self.cols[q.column][keep],
                            minlength=card)
        return count, total / np.maximum(count, 1)


def suite():
    queries = {name: make() for name, make in fq.ALL.items()}
    queries["F-q9/dkw"] = fq.f_q9(bounder="anderson_dkw", rangetrim=False)
    return queries


def check_answer(label, q, res, ref, span):
    """Every view's interval brackets the truth, fully covered views
    equal it, and the stopping condition's answer is the true one."""
    count, mean = ref.avg(q)
    count, mean = count[res.group_codes], mean[res.group_codes]
    has = count > 0
    tol = F32_TOL * span
    lo, hi, est, truth = (res.lo[has], res.hi[has], res.estimate[has],
                          mean[has])
    if not (np.isfinite(lo).all() and np.isfinite(hi).all()):
        fail(f"{label}: non-finite interval")
    miss = (truth < lo - tol) | (truth > hi + tol)
    if miss.any():
        i = int(np.argmax(miss))
        fail(f"{label}: {int(miss.sum())} intervals miss the truth, e.g. "
             f"[{lo[i]}, {hi[i]}] vs {truth[i]}")
    exact = res.exact[has]
    if (np.abs(est - truth)[exact] > tol).any():
        fail(f"{label}: an exact view's estimate is off the truth")
    codes = res.group_codes[has]
    if isinstance(q.stop, TopKSeparated):
        k, largest = q.stop.k, q.stop.largest
        order = np.argsort(-truth if largest else truth)
        want = set(codes[order[:k]].tolist())
        got = set(res.topk(k, largest).tolist())
        if got != want:
            fail(f"{label}: top-{k} {sorted(got)} != true {sorted(want)}")
    if isinstance(q.stop, GroupsOrdered) and res.stopped_early:
        seen = res.nonempty[has]
        want = codes[seen][np.argsort(truth[seen])].tolist()
        got = [c for c in res.order().tolist() if c in set(want)]
        if want != got:
            fail(f"{label}: group order {got} != true {list(want)}")
    return int(exact.sum()), int(has.sum())


def report(label, res, nb, wall=None, compile_s=None):
    timing = ("" if wall is None else f" wall_s={wall:.3f} "
              f"compile_s={compile_s:.3f} (smoke timing)")
    print(f"{label:<24s} blocks_fetched={res.blocks_fetched}/{nb} "
          f"rounds={res.rounds} stopped_early={res.stopped_early}{timing}",
          flush=True)


def timed(clock, fn):
    t0, c0 = time.perf_counter(), clock.seconds
    out = fn()
    return out, time.perf_counter() - t0, clock.seconds - c0


def check_scheduler_log(log):
    kinds = [e[2] for e in log]
    bad = sorted(RECOVERY_EVENTS.intersection(kinds))
    if bad:
        fail(f"scheduler logged recovery events {bad}")
    return kinds


def one_chip(sc, ref, impl, clock):
    """FastFrame.run, FrameServer.run_batch and QueryScheduler over the
    whole suite on one device."""
    cfg = EngineConfig(impl=impl, shard_rows=False)
    if not cfg.resolve_device_loop():
        fail("the device-resident round loop is off")
    frame = FastFrame(sc, cfg)
    a, b = sc.catalog["dep_delay"]
    span = b - a
    nb = sc.n_blocks
    queries = suite()
    names = list(queries)

    print("== FastFrame.run", flush=True)
    for name in names:
        res, wall, comp = timed(clock, lambda: frame.run(queries[name],
                                                          start_block=0))
        report(f"{name} run", res, nb, wall, comp)
        exact, views = check_answer(f"{name} run", queries[name], res,
                                    ref, span)
        print(f"    views={views} exact={exact} brackets=ok", flush=True)

    print("== FrameServer.run_batch", flush=True)
    batch, wall, comp = timed(clock, lambda: FrameServer(frame).run_batch(
        [queries[n] for n in names], start_block=0))
    print(f"batch of {len(names)}: wall_s={wall:.3f} compile_s={comp:.3f} "
          "(smoke timing)", flush=True)
    for name, res in zip(names, batch):
        report(f"{name} run_batch", res, nb)
        check_answer(f"{name} run_batch", queries[name], res, ref, span)

    print("== QueryScheduler", flush=True)
    sched = QueryScheduler(FrameServer(frame), clock=WallClock(),
                           start_block=0)
    tickets = {n: sched.submit(queries[n]) for n in SCHEDULER_QUERIES}
    _, wall, comp = timed(clock, sched.run_until_idle)
    kinds = check_scheduler_log(sched.log)
    print(f"scheduler: {len(tickets)} tickets wall_s={wall:.3f} "
          f"compile_s={comp:.3f} (smoke timing) events={len(kinds)}",
          flush=True)
    for name, tk in tickets.items():
        if tk.status != "done" or tk.partial:
            fail(f"{name}: ticket ended {tk.status!r} "
                 f"(partial={tk.partial})")
        report(f"{name} scheduler", tk.result, nb)
        # a query admitted after the pass's first rounds scans from where
        # it joined, like frame.run(q, start_block=joined_at)
        print(f"    joined_at_block={tk._qc.slot.anchor % nb}", flush=True)
        check_answer(f"{name} scheduler", queries[name], tk.result, ref,
                     span)


def device_bytes():
    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


def four_chips(sc, ref, impl, clock):
    """The divided scan over a (4,) mesh at merge_every 1 and 4 against
    the single-device device loop."""
    a, b = sc.catalog["dep_delay"]
    span = b - a
    nb = sc.n_blocks
    queries = {n: q for n, q in suite().items() if n in SHARDED_QUERIES}
    runs = {}
    for label, over in (("shards=4 merge_every=1",
                         dict(shard_rows=True, mesh_shape=(4,))),
                        ("single device", dict(shard_rows=False)),
                        ("shards=4 merge_every=4",
                         dict(shard_rows=True, mesh_shape=(4,),
                              merge_every=4))):
        frame = FastFrame(sc, EngineConfig(impl=impl, **over))
        print(f"== {label}", flush=True)
        for name, q in queries.items():
            res, wall, comp = timed(clock, lambda: frame.run(q,
                                                              start_block=0))
            report(f"{name} {label}", res, nb, wall, comp)
            check_answer(f"{name} {label}", q, res, ref, span)
            runs[label, name] = res
        if over.get("merge_every", 1) == 1 and over["shard_rows"]:
            used = device_bytes()
            print("bytes_in_use per device after the sharded runs: "
                  f"{used}", flush=True)
            if min(used) * 2 < max(used):
                fail("row slabs are not split evenly across the mesh")
        del frame

    tol = F32_TOL * span
    for name in queries:
        base = runs["single device", name]
        k1 = runs["shards=4 merge_every=1", name]
        for f in SCAN_FIELDS:
            if not np.array_equal(getattr(k1, f), getattr(base, f)):
                fail(f"{name}: merge_every=1 {f} differs from the single "
                     "device loop")
        for f in ("lo", "hi", "estimate"):
            if not np.allclose(getattr(k1, f), getattr(base, f),
                               rtol=SHARD_CI_RTOL, atol=tol):
                fail(f"{name}: merge_every=1 {f} beyond the f32 reorder "
                     "bound")
        k4 = runs["shards=4 merge_every=4", name]
        if k4.rounds < base.rounds or k4.stopped_early != base.stopped_early:
            fail(f"{name}: merge_every=4 stopped at round {k4.rounds} "
                 f"(single device: {base.rounds})")
        print(f"{name}: merge_every=1 scan decisions identical to the "
              f"single device; merge_every=4 rounds {k4.rounds} vs "
              f"{base.rounds}, blocks {k4.blocks_fetched} vs "
              f"{base.blocks_fetched}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=100_000_000,
                    help="synthetic FLIGHTS rows")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the divided scan and its comparison")
    args = ap.parse_args(argv)

    jax.config.update("jax_enable_x64", True)
    cache = enable_compile_cache()
    dev, impl = require_tpu(args.chips)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"kernels={impl} compile_cache={cache}", flush=True)
    clock = CompileClock()

    t0 = time.perf_counter()
    ds = flights.generate(n_rows=args.rows, seed=args.seed)
    t1 = time.perf_counter()
    sc = build_scramble(ds.columns, catalog=ds.catalog,
                        block_rows=BLOCK_ROWS, seed=args.seed + 1)
    t2 = time.perf_counter()
    print(f"data: FLIGHTS rows={args.rows} blocks={sc.n_blocks} "
          f"block_rows={BLOCK_ROWS} (reduced from the paper's {PAPER_ROWS} "
          "rows, §5.1 Table 3, to keep host generation near a minute); "
          f"generate_s={t1 - t0:.1f} scramble_s={t2 - t1:.1f} "
          "(smoke timing)", flush=True)
    ref = Reference(ds.columns)

    if args.chips == 4:
        four_chips(sc, ref, impl, clock)
    else:
        one_chip(sc, ref, impl, clock)

    stats = dev.memory_stats() or {}
    print(f"compiles={clock.count} compile_s={clock.seconds:.1f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
