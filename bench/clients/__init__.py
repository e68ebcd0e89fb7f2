"""Clients: one module per way of asking the program, each a
``warm_up(frame, mix, seed)`` and a ``ask(session)`` (see
``bench.harness``). A traffic mix names its client."""
