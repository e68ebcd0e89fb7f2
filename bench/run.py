"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, traffic
mix, limits and metrics are named in ``BENCHMARK.json`` (see
``bench/harness.py``). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``,
each compared number beside its limit. Those numbers are also the last
lines of standard error.

The run needs a TPU with at least the chips the cell asks for: without
one it exits non-zero and prints no result. JAX's persistent compilation
cache is kept in ``.bench_cache/jax`` at the root of the checkout,
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def fail(msg: str, code: int = 1):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def require_chips(jax, chips: int):
    """The first device, which has to be a TPU, with ``chips`` of them."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        fail(f"the cell asks for {chips} chips; JAX sees {len(devices)}")
    return devices[0]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative whole number", 2)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program (src/repro) is not in {ROOT}", 2)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))

    import jax
    jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"bench: compile cache {jax.config.jax_compilation_cache_dir}",
          file=sys.stderr, flush=True)

    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    device = require_chips(jax, cell.chips)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, device)
    print(json.dumps(harness.json_safe(out)), flush=True)


if __name__ == "__main__":
    main()
