"""The control of the correctness check, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's table as a run does, and answers the
questions of one cycle of the cell's mix with the control in the
program's place: the plain reference computed one precision lower
(``Reference.control_view``: per-round partial sums kept in a float32
running state). Every control answer is exact, and is judged by
``bench/check.py`` against the float64 reference exactly as the
program's answers are. It prints one JSON line
per seed with the numbers compared, and a last line with the smallest
of each over the seeds (the upper reading a limit is set below) and
whether every seed failed at least one limit.

Like ``run.py`` it needs the chip: the table is made on the device.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _log_memory(stage: str) -> None:
    """The process's peak host memory so far, on standard error."""
    import resource

    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(f"control: {stage} host_peak_bytes={peak}", file=sys.stderr,
          flush=True)


def control_numbers(cell, seed: int, limits: dict) -> dict:
    """The control's numbers on one seed of ``cell``."""
    import importlib

    from bench import check, traffic
    from bench.reference import Reference, group_cols

    cfg = cell.config
    gen = importlib.import_module(f"bench.generators.{cfg['generator']}")
    columns, _ = gen.generate(cfg, seed)
    read = {c for t in cell.mix["templates"].values()
            for c in (t["column"], *group_cols(t),
                      *(f[0] for f in t.get("filters", ())))}
    columns = {k: v for k, v in columns.items() if k in read}
    _log_memory(f"seed {seed} generated")
    ref = Reference(columns, cfg["rows"], gen.categorical(cfg))
    round_rows = cfg["engine"]["round_blocks"] * cfg["block_rows"]
    per_answer = []
    n_blocks = -(-cfg["rows"] // cfg["block_rows"])
    for req in traffic.cycle(cell.mix, seed, 1, n_blocks):
        tpl = req.spec
        a, b = gen.catalog()[tpl["column"]]
        count, mean = ref.view(tpl)
        _, cmean = ref.control_view(tpl, 0.5 * (a + b), round_rows)
        answer = {"estimate": cmean, "lo": cmean, "hi": cmean,
                  "exact": count > 0}
        per_answer.append(check.judge(tpl, answer, count, mean,
                                      limits["exact_gap"]))
        _log_memory(f"seed {seed} {req.template}")
    numbers, failed, correct = check.summarize(per_answer, limits)
    return dict(seed=seed, correct=correct, failed=failed, **numbers)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    import jax
    jax.config.update("jax_enable_x64", True)

    from bench import check, harness
    from bench.run import require_chips
    cell = harness.load_cell(ROOT, args.workload)
    require_chips(jax, cell.chips)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(control_numbers(cell, seed, cell.limits))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"upper": {k: min(r[k] for r in rows)
                                for k in check.NUMBERS},
                      "limits": cell.limits,
                      "all_fail": not any(r["correct"] for r in rows)}))


if __name__ == "__main__":
    main()
