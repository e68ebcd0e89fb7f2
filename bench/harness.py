"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to a cell is found by name: the configuration's
file (``BENCHMARK.json``), its generator (``bench/generators/<name>.py``),
the traffic mix (``bench/traffic/<mix>.json``, read by
``bench/traffic.py``), the client the mix names
(``bench/clients/<client>.py``), the limits of the correctness check
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``, a function ``read(run)`` that returns a
number, or ``None`` where it finds nothing to read).

A client is a module with two functions:

* ``warm_up(frame, mix, seed)``: runs, untimed, every program the
  window will run on that frame, through the program's public calls;
* ``ask(session)``: asks the questions of the window through
  ``Session.ask`` (see ``Session``).

Set-up (``setup_s``) runs from process start to the first timed query:
the table made on the chip from the seed, the engine's frame over it,
and the client's warm-up. The window then runs the client for
``--seconds``. After the window the device's peak memory is read, the
frame is freed and every answer is compared with the plain reference.

With ``trace`` the profiler records the first answers of the window's
first cycle, those of the templates the mix's ``trace`` names (the
generator puts them first), and the per-layer metrics are read from
them; the end-to-end metrics are taken with the profiler off, in runs
without ``trace``. A traced run whose trace misses device work of a
traced answer (the profiler's buffer overflowed) exits with an error
and no result.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from bench import check, tracing, traffic
from bench.reference import Reference

BENCH = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
# A traced answer of this many rounds or more keeps the device busy most
# of its span; under half busy, the profiler has dropped its operations.
LONG_ROUNDS = 1000


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _listed(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    traffic.validate(mix)
    return Cell(
        name=workload, chips=w["chips"],
        config=json.loads((root / cfg_entry["file"]).read_text()), mix=mix,
        limits=json.loads((BENCH / "limits" / f"{workload}.json")
                          .read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _listed(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _listed(m, workload)])


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    """The chip's HBM bandwidth from ``bench/peaks.json``; a kind that is
    not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return float(table[device_kind]["hbm_bytes_per_s"])


class CompileCounter:
    """Backend compiles, their seconds, and persistent-cache hits and
    misses, as JAX reports them; ``mark`` names the phase that ends."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.marks = {}

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == HIT_EVENT:
            self.hits += 1
        elif event == MISS_EVENT:
            self.misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def mark(self, phase: str) -> None:
        self.marks[phase] = dict(compiles=self.compiles,
                                 compile_s=self.seconds, cache_hits=self.hits,
                                 cache_misses=self.misses)


@dataclasses.dataclass
class Answer:
    """One answered query of the window."""

    template: str
    spec: dict          # the template as asked, drawn values filled in
    start: int
    cycle: int
    span: str           # its benchmark span in the trace
    t_asked: float
    t_done: float
    blocks_fetched: int
    rounds: int
    stopped_early: bool
    bytes_needed: int   # value, group code and predicate bytes it folds

    @property
    def ms(self) -> float:
        return (self.t_done - self.t_asked) * 1e3


@dataclasses.dataclass
class RunRecord:
    """What the per-layer metric readers read."""

    answers: list
    traced: list            # the traced answers
    window: object          # tracing.Window over them, or None
    compiles_in_window: int
    peak_bytes: object      # int, or None where the backend has no stats
    hbm_bytes_per_s: float


def bytes_per_row(spec: dict) -> int:
    """Bytes the fold needs per row: the value, the group code where the
    query groups, the predicate where it filters."""
    return 4 + 4 * bool(spec.get("group_by")) + 4 * bool(spec.get("filters"))


class Session:
    """The measured window as a client sees it.

    ``cycles()`` yields ``(index, requests)`` for one whole cycle of the
    mix at a time while the last cycle's length says the next ends within
    ``seconds``, and always at least one. ``ask(requests, call)`` makes
    one call to the program that answers ``requests``, under one
    benchmark span, and records an answer for each from the
    ``QueryResult`` list the call returns. ``query(request)`` is the
    program's query object for a request.

    In a traced run the profiler starts with the first cycle, whose
    traced requests come first, and stops once they are answered.
    """

    def __init__(self, cell: Cell, frame, seed: int, seconds: float,
                 trace_dir: Optional[str]):
        self.cell, self.frame, self.seed = cell, frame, seed
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.n_blocks = frame.scramble.n_blocks
        self.answers, self.results, self.traced = [], [], []
        self.cycle = 0
        self._queries = {}
        self._to_trace = 0

    def requests(self, index: int) -> list:
        return traffic.cycle(self.cell.mix, self.seed, index, self.n_blocks,
                             traced=self.trace_dir is not None
                             and index == 1)

    def query(self, request: traffic.Request):
        key = json.dumps(request.spec, sort_keys=True)
        if key not in self._queries:
            self._queries[key] = traffic.build_query(request.spec)
        return self._queries[key]

    def cycles(self):
        t0, last = time.perf_counter(), 0.0
        self.cycle = 1
        while (not self.answers
               or time.perf_counter() - t0 + last <= self.seconds):
            t_cycle = time.perf_counter()
            reqs = self.requests(self.cycle)
            self._to_trace = sum(r.traced for r in reqs)
            if self._to_trace:
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=tracing.options())
            with jax.profiler.TraceAnnotation(f"bench:cycle {self.cycle}"):
                yield self.cycle, reqs
            last = time.perf_counter() - t_cycle
            self.cycle += 1

    def ask(self, requests: list, call, due: Optional[float] = None) -> list:
        """``call()`` answers ``requests``; an answer's time runs from
        ``due`` (a ``time.perf_counter`` reading) where the requests were
        due before they were asked, else from the call."""
        span = (f"bench:answer {len(self.answers)} "
                + ",".join(r.template for r in requests))
        with jax.profiler.TraceAnnotation(span):
            t_asked = time.perf_counter()
            results = call()
            t_done = time.perf_counter()
        block_rows = self.frame.scramble.block_rows
        for req, res in zip(requests, results, strict=True):
            self.answers.append(Answer(
                template=req.template, spec=req.spec, start=req.start,
                cycle=self.cycle, span=span,
                t_asked=t_asked if due is None else min(due, t_asked),
                t_done=t_done, blocks_fetched=int(res.blocks_fetched),
                rounds=int(res.rounds),
                stopped_early=bool(res.stopped_early),
                bytes_needed=int(res.blocks_fetched) * block_rows
                * bytes_per_row(req.spec)))
            self.results.append({k: np.array(getattr(res, k)) for k in
                                 ("estimate", "lo", "hi", "exact")})
            if req.traced:
                self.traced.append(self.answers[-1])
                self._to_trace -= 1
                if not self._to_trace:
                    t = time.perf_counter()
                    jax.profiler.stop_trace()
                    log(f"trace: stop_trace_s={time.perf_counter() - t}")
        return results


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(answers: list, window_t0: float, setup_s: float) -> dict:
    ms = [a.ms for a in answers]
    last = max(a.t_done for a in answers)
    return {"answer_ms_p50": percentile(ms, 50),
            "answer_ms_p95": percentile(ms, 95),
            "answers_per_s": len(answers) / (last - window_t0),
            "setup_s": setup_s}


def read_per_layer(metrics: list, run: RunRecord) -> dict:
    out = {}
    for m in metrics:
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def trace_gap(win: tracing.Window, traced: list) -> Optional[str]:
    """Why the trace misses device work of a traced answer, or ``None``
    where every traced answer's span holds its device operations."""
    for a in traced:
        try:
            s, e = win.trace.span(a.span)
        except KeyError:
            return f"{a.span!r} is not in the trace"
        busy = win.busy_s(s, e)
        if busy <= 0:
            return f"no device operation inside {a.span!r}"
        if a.rounds >= LONG_ROUNDS and busy < 0.5 * (e - s) * 1e-9:
            return (f"{a.span!r} ({a.rounds} rounds) is busy {busy} s of "
                    f"{(e - s) * 1e-9} s")
    return None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device, keep_trace: Optional[Path] = None
             ) -> dict:
    """One run; returns the result line's object. With ``keep_trace`` a
    traced run writes its profile there and keeps it."""
    cfg = cell.config
    gen = importlib.import_module(f"bench.generators.{cfg['generator']}")
    client = importlib.import_module(f"bench.clients.{cell.mix['client']}")
    hbm_peak = peak_hbm_bytes_per_s(device.device_kind)
    limits = cell.limits
    with CompileCounter() as cc:
        frame, columns = _set_up(cell, gen, client, seed, t_start, cc)
        setup_s = time.perf_counter() - t_start
        trace_dir = None
        if trace:
            trace_dir = str(keep_trace or tempfile.mkdtemp(
                prefix="bench-trace-"))
        window_t0 = time.perf_counter()
        session = Session(cell, frame, seed, seconds, trace_dir)
        client.ask(session)
        cc.mark("window")
    answers, results, traced = (session.answers, session.results,
                                session.traced)
    window_compiles = (cc.marks["window"]["compiles"]
                       - cc.marks["setup"]["compiles"])
    window_hits = (cc.marks["window"]["cache_hits"]
                   - cc.marks["setup"]["cache_hits"])
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    for a in answers:
        log(f"answer {a.template} cycle={a.cycle} start={a.start} "
            f"ms={a.ms} blocks={a.blocks_fetched} rounds={a.rounds} "
            f"stopped_early={a.stopped_early}")
    log(f"window: answers={len(answers)} cycles={answers[-1].cycle} "
        f"compiles={window_compiles} cache_hits={window_hits} "
        f"peak_bytes_in_use={peak}")
    t = time.perf_counter()
    del frame, session
    gc.collect()
    t_free = time.perf_counter() - t

    t = time.perf_counter()
    ref = Reference(columns, cfg["rows"], gen.categorical(cfg))
    per_answer = []
    for a, res in zip(answers, results):
        count, mean = ref.view(a.spec)
        per_answer.append(check.judge(a.spec, res, count, mean,
                                      limits["exact_gap"]))
    numbers, failed, correct = check.summarize(per_answer, limits)
    log(f"check: free_s={t_free} reference_s={time.perf_counter() - t}")

    out = {"correct": correct, "attempted": len(answers), "failed": failed}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        t = time.perf_counter()
        tr = _read_trace(trace_dir, keep=keep_trace is not None)
        log(f"trace: read_s={time.perf_counter() - t} ops="
            f"{sum(len(o[0]) for o in tr.ops)} answers={len(traced)}")
        win = tracing.Window(tr, tr.span(traced[0].span)[0],
                             tr.span(traced[-1].span)[1])
        gap = trace_gap(win, traced)
        if gap is not None:
            raise SystemExit(f"bench: the trace is incomplete: {gap}")
        run = RunRecord(answers=answers, traced=traced, window=win,
                        compiles_in_window=window_compiles, peak_bytes=peak,
                        hbm_bytes_per_s=hbm_peak)
        out["metrics"] = read_per_layer(cell.per_layer, run)
        dev.update(busy_s=win.busy_s(), window_s=win.window_s)
        out["device"] = dev
        out["breakdown"] = {"device_ops": win.device_ops(),
                            "idle_gaps": win.idle_gaps()}
    else:
        e2e = end_to_end(answers, window_t0, setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = dev
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in check.NUMBERS}
    for k in check.NUMBERS:
        log(f"check {k}: {numbers[k]} (limit {limits[k]})")
    return out


def _set_up(cell: Cell, gen, client, seed: int, t_start: float,
            cc: CompileCounter):
    """The table made from the seed, the frame over it, and the client's
    warm-up; returns ``(frame, columns)``."""
    from repro.aqp import EngineConfig, FastFrame
    from repro.aqp.scramble import Scramble
    from repro.kernels import ops as kops

    cfg = cell.config
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:generate"):
        columns, valid = gen.generate(cfg, seed)
    t_gen = time.perf_counter() - t
    scramble = Scramble(columns=dict(columns), valid=valid,
                        n_rows=cfg["rows"], block_rows=cfg["block_rows"],
                        catalog=dict(gen.catalog()),
                        categorical=dict(gen.categorical(cfg)), seed=seed)
    frame = FastFrame(scramble, EngineConfig(**cfg["engine"]))
    cc.mark("generate")
    t = time.perf_counter()
    client.warm_up(frame, cell.mix, seed)
    cc.mark("setup")
    log(f"setup: rows={cfg['rows']} blocks={scramble.n_blocks} kernels="
        f"{kops.resolve_impl(cfg['engine'].get('impl'))} generate_s={t_gen} "
        f"warmup_s={time.perf_counter() - t} "
        f"setup_s={time.perf_counter() - t_start}")
    log(f"setup compiles: {json.dumps(cc.marks)}")
    return frame, columns


def _read_trace(trace_dir: str, keep: bool) -> tracing.Trace:
    """The profiler's trace; its files are removed unless ``keep``."""
    try:
        return tracing.load(tracing.find_xplane(trace_dir))
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)


def json_safe(obj):
    """``obj`` with every non-finite float written as a string, so that
    the result line is strict JSON."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    if isinstance(obj, dict):
        return {k: json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    return obj
