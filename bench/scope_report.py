"""Run one cell's traced run and break it down by the program's spans and
named scopes.

    python3 bench/scope_report.py --workload flights-606m.wholetable-solo \
        --seed 7
    python3 bench/scope_report.py --workload flights-151m.suite-solo \
        --rows 1000000 --templates F-q9,F-q9/dkw \
        --keep bench/tests/data/trace_scopes.xplane.pb.gz

It runs one cycle of the cell through the harness as ``bench/run.py
--trace 1`` does (the profiler over its traced answers), with HLO protos in
the profile (``bench/scopes.py``'s ``options``). It then reads the
profile with ``bench/scopes.py`` and logs, on standard error, each
traced answer's program phases, the device seconds per named scope with
the share of busy time no scope holds, the largest operations with
their scopes, and the idle gaps by the innermost span. The last line of
standard output is the run's result line with the per-layer numbers of
``scopes.METRICS`` added under ``"scopes"``.

``--rows`` and ``--templates`` cut the table and the mix, and the
templates cut to are all traced; ``--keep`` writes the profile there,
gzipped. Needs the chip. JAX's persistent compilation cache is the one
``bench/run.py`` keeps.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--templates")
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    jax.config.update("jax_enable_x64", True)

    from bench import harness, scopes, tracing
    from bench.run import CACHE_DIR, require_chips

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cell = harness.load_cell(ROOT, args.workload)
    device = require_chips(jax, cell.chips)
    if args.rows:
        cell.config["rows"] = args.rows
    if args.templates:
        names = args.templates.split(",")
        cell.mix["templates"] = {k: cell.mix["templates"][k] for k in names}
        cell.mix["trace"] = names
    tmp = tempfile.mkdtemp(prefix="bench-scopes-")
    # the profile with HLO protos, and the harness's own run record (its
    # traced answers and their rounds) as its metric readers get it
    plain_options, plain_read = tracing.options, harness.read_per_layer
    runs = []

    def read_per_layer(metrics, run):
        runs.append(run)
        return plain_read(metrics, run)

    tracing.options, harness.read_per_layer = scopes.options, read_per_layer
    try:
        out = harness.run_cell(cell, args.seed, 0.0, True, T_START, device,
                               keep_trace=Path(tmp))
        path = tracing.find_xplane(tmp)
        traced = runs[0].traced
        trace = scopes.load(path)
        win = scopes.Window(trace, trace.span(traced[0].span)[0],
                            trace.span(traced[-1].span)[1])
        for line in scopes.report(win, traced):
            harness.log(line)
        out["scopes"] = scopes.numbers(win, traced)
        if args.keep:
            Path(args.keep).parent.mkdir(parents=True, exist_ok=True)
            Path(args.keep).write_bytes(gzip.compress(path.read_bytes()))
    finally:
        tracing.options, harness.read_per_layer = plain_options, plain_read
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(harness.json_safe(out)), flush=True)


if __name__ == "__main__":
    main()
