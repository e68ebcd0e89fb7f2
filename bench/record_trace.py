"""Record the small chip trace that ``bench/tests/test_tracing.py`` reads.

    python3 bench/record_trace.py --out bench/tests/data

It runs the cell ``flights-151m.suite-solo`` through the harness as a
traced run, cut to a 2M-row table and two templates (F-q2, which stops
early, and F-q9, which scans to the exact answer) and to one cycle,
keeps the profile, and writes it gzipped to ``--out/trace.xplane.pb.gz``.
It prints the run's result line. Needs the chip.
"""

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
WORKLOAD = "flights-151m.suite-solo"
TEMPLATES = ("F-q2", "F-q9")
ROWS = 2_000_000


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    import jax
    jax.config.update("jax_enable_x64", True)

    from bench import harness, tracing
    from bench.run import require_chips

    cell = harness.load_cell(ROOT, WORKLOAD)
    device = require_chips(jax, cell.chips)
    cell.config["rows"] = ROWS
    cell.mix["templates"] = {k: cell.mix["templates"][k] for k in TEMPLATES}
    cell.mix["trace"] = list(TEMPLATES)
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        out = harness.run_cell(cell, args.seed, 0.0, True, T_START, device,
                               keep_trace=Path(tmp))
        dest = Path(args.out)
        dest.mkdir(parents=True, exist_ok=True)
        (dest / "trace.xplane.pb.gz").write_bytes(
            gzip.compress(tracing.find_xplane(tmp).read_bytes()))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(harness.json_safe(out)))


if __name__ == "__main__":
    main()
