"""One analyst in a closed loop through ``FastFrame.run``.

The analyst asks the next question when the last answer arrives, with no
think time, in whole cycles of the mix (``bench.harness.Session``).

Warm-up runs each template of cycle 0 to its end, in the order of the
templates' names: the persistent cache's keys for the engine's loops
change with the order in which a process compiles them, and every run
has to find them again. It then runs one round of each template's exact
sweep (``sampling="exact"``): that round folds one lookahead batch of
host-fed blocks, the same fold as the recovery pass, which a served
query reaches only when a skipped view turns active again.
"""

import jax

from bench import traffic


def warm_up(frame, mix: dict, seed: int) -> None:
    reqs = sorted(traffic.cycle(mix, seed, 0, frame.scramble.n_blocks),
                  key=lambda r: r.template)
    for r in reqs:
        with jax.profiler.TraceAnnotation(f"bench:warmup {r.template}"):
            frame.run(traffic.build_query(r.spec), start_block=r.start)
    for r in reqs:
        with jax.profiler.TraceAnnotation(f"bench:warmup {r.template} "
                                          "exact round"):
            frame.run(traffic.build_query(r.spec), sampling="exact",
                      max_rounds=1)


def ask(session) -> None:
    frame = session.frame
    for _, reqs in session.cycles():
        for r in reqs:
            q = session.query(r)
            session.ask([r], lambda: [frame.run(q, start_block=r.start)])
