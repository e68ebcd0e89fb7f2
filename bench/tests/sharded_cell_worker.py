"""One cycle of ``flights-606m-4chip.suite-solo`` at a small size on four
CPU devices, through ``FastFrame.run``, divided and on one device.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python bench/tests/sharded_cell_worker.py <rows> <seed>

The frame is built by the harness's own set-up (``harness._set_up``:
the cell's generator's table cut to ``rows``, the frame from the
configuration's ``engine`` block, the client's warm-up); the same table
and the same cycle of questions are answered again with the scan on one
device (``shard_rows`` off, set up with no warm-up). Both fold with the chip's
Pallas kernels in interpret mode (``impl="interpret"``; the block
leaves ``impl`` to the platform, which on a CPU picks XLA's float32
scatter-add, whose exact views sat up to 3.4e-4 minutes off the truth
at 400k rows over four seeds, against at most 6.7e-5 for the kernels
over three). Every answer of the divided
run is judged with ``bench/check.judge`` against ``bench/reference.py``
at the cell's ``exact_gap`` limit. The last line of standard output is a
JSON object: ``shards`` (the frame's shard count) and, per template, its
rounds and blocks in both runs and the divided run's judged numbers.
``bench/tests/test_sharded_cell.py`` runs it in a subprocess, since the
device count is fixed when JAX starts.
"""

import dataclasses
import importlib
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "flights-606m-4chip.suite-solo"


def _answers(cell, engine: dict, seed: int, warm_up: bool):
    """``(frame, columns, [(request, QueryResult)])``: the frame the
    harness sets up for ``cell`` with ``engine`` in its configuration
    (the client's warm-up left out unless ``warm_up``), and its answers
    to cycle 1 of the cell's mix."""
    from bench import harness, traffic

    cell = dataclasses.replace(cell, config=dict(cell.config, engine=engine))
    gen = importlib.import_module(
        f"bench.generators.{cell.config['generator']}")
    client = importlib.import_module(f"bench.clients.{cell.mix['client']}")
    if not warm_up:
        client = types.SimpleNamespace(warm_up=lambda *_: None)
    frame, columns = harness._set_up(cell, gen, client, seed,
                                     time.perf_counter(),
                                     harness.CompileCounter())
    reqs = traffic.cycle(cell.mix, seed, 1, frame.scramble.n_blocks)
    return frame, columns, [(r, frame.run(traffic.build_query(r.spec),
                                          start_block=r.start))
                            for r in reqs]


def main(rows: int, seed: int) -> dict:
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    import jax
    jax.config.update("jax_enable_x64", True)
    from bench import check, harness
    from bench.generators import flights
    from bench.reference import Reference

    cell = harness.load_cell(ROOT, CELL)
    cell = dataclasses.replace(cell, config=dict(cell.config, rows=rows))
    engine = dict(cell.config["engine"], impl="interpret")
    frame, columns, divided = _answers(cell, engine, seed, warm_up=True)
    one_engine = {k: v for k, v in engine.items()
                  if k not in ("mesh_shape", "merge_every")}
    _, _, one = _answers(cell, dict(one_engine, shard_rows=False), seed,
                         warm_up=False)
    ref = Reference(columns, rows, flights.categorical(cell.config))
    out = {"shards": frame.block_shards().n_shards, "templates": {}}
    for (req, res), (req1, res1) in zip(divided, one, strict=True):
        assert req == req1
        count, mean = ref.view(req.spec)
        out["templates"][req.template] = dict(
            rounds=int(res.rounds), blocks=int(res.blocks_fetched),
            one_rounds=int(res1.rounds), one_blocks=int(res1.blocks_fetched),
            **check.judge(req.spec, res, count, mean,
                          cell.limits["exact_gap"]))
    return out


if __name__ == "__main__":
    print(json.dumps(main(int(sys.argv[1]), int(sys.argv[2]))))
