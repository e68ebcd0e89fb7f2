"""Gather and fold: the share of the chip's HBM bandwidth that the fold's
own bytes take of the traced answers' device-busy time.

The bytes count the work, not the implementation: per fetched block's
rows, 4 B of value, 4 B of group code where the query groups and 4 B of
predicate where it filters (``bench.harness.bytes_per_row``). Gather
copies, padding and zero slabs are not counted, so the share cannot
pass 100% unless the busy time misses work."""


def read(run):
    if run.window is None:
        return None
    busy = run.window.busy_s()
    need = sum(a.bytes_needed for a in run.traced)
    if busy <= 0 or need <= 0:
        return None
    return 100.0 * need / (run.hbm_bytes_per_s * busy)
