"""The plain reference: exact per-group aggregates with numpy alone.

It reads the benchmark's own generated columns and a template's data
(``filters``, ``group_by``, ``column``), never the program: filters are
applied row by row, composite group codes are formed from the
configuration's cardinalities (``code = code * card + column``, the
order the template lists), and the count and sum of each group are
taken by ``np.bincount`` in float64, over chunks of rows so that the
temporaries stay small.

``control_view`` is the same computation one precision lower: the
per-round partial sums (``round_rows`` rows, about the catalog midpoint
as the engine folds them) are kept in a float32 running state, which is
what an engine that dropped its float64 merge would hold.
"""

from __future__ import annotations

import operator
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# A chunk's temporaries (up to about 60 B a row) stay a few MB each, so the
# allocator reuses them; with 16M-row chunks, passes over a 606M-row table
# ran a one-chip machine's 40 GiB of host memory out.
CHUNK_ROWS = 1 << 20
THREADS = min(8, os.cpu_count() or 1)
COMPARE = {"eq": operator.eq, "ne": operator.ne, "gt": operator.gt,
           "ge": operator.ge, "lt": operator.lt, "le": operator.le}


def group_cols(tpl: dict) -> tuple:
    g = tpl.get("group_by") or ()
    return (g,) if isinstance(g, str) else tuple(g)


def view_key(tpl: dict) -> tuple:
    return (tuple(tuple(f) for f in tpl.get("filters", ())),
            group_cols(tpl), tpl["column"])


class Reference:
    """Exact ``(count, mean)`` per group code of a template's view, over
    the first ``n_rows`` rows of ``columns`` (flat or blocked arrays)."""

    def __init__(self, columns: dict, n_rows: int, cards: dict):
        self.cols = {k: v.reshape(-1)[:n_rows] for k, v in columns.items()}
        self.n = n_rows
        self.cards = cards
        self._views = {}

    def n_groups(self, tpl: dict) -> int:
        g = 1
        for c in group_cols(tpl):
            g *= self.cards[c]
        return g

    def _chunk(self, tpl: dict, lo: int, hi: int):
        """``(group codes, values, keep)`` of the rows ``lo:hi``, codes and
        values of the rows the template's filters keep; ``keep`` is their
        mask over ``lo:hi``, or ``None`` where it has no filter."""
        keep = None
        for col, op, val in tpl.get("filters", ()):
            hit = COMPARE[op](self.cols[col][lo:hi], val)
            keep = hit if keep is None else keep & hit
        g = group_cols(tpl)
        if not g:
            code = np.zeros(hi - lo, np.intp)
        elif len(g) == 1:
            code = self.cols[g[0]][lo:hi]
        else:
            code = np.zeros(hi - lo, np.int64)
            for c in g:
                code = code * self.cards[c] + self.cols[c][lo:hi]
        val = self.cols[tpl["column"]][lo:hi]
        if keep is not None:
            code, val = code[keep], val[keep]
        return code, val, keep

    def _map(self, fn, step: int = CHUNK_ROWS) -> list:
        """``fn(lo, hi)`` over the row chunks, in row order."""
        bounds = [(lo, min(lo + step, self.n)) for lo in range(0, self.n,
                                                                 step)]
        with ThreadPoolExecutor(THREADS) as ex:
            return list(ex.map(lambda b: fn(*b), bounds))

    def view(self, tpl: dict):
        """``(count, mean)`` arrays of length ``n_groups(tpl)``, float64;
        computed once per distinct (filters, group-by, column)."""
        key = view_key(tpl)
        if key not in self._views:
            G = self.n_groups(tpl)

            def part(lo, hi):
                code, val, _ = self._chunk(tpl, lo, hi)
                return (np.bincount(code, minlength=G),
                        np.bincount(code, weights=val, minlength=G))

            count = np.zeros(G, np.float64)
            total = np.zeros(G, np.float64)
            for c, t in self._map(part):
                count += c
                total += t
            self._views[key] = (count, total / np.maximum(count, 1.0))
        return self._views[key]

    def control_view(self, tpl: dict, center: float, round_rows: int):
        """``(count, mean)`` of the control: exact per-round partial sums
        about ``center``, accumulated round after round in float32."""
        G = self.n_groups(tpl)

        def part(lo, hi):
            code, val, keep = self._chunk(tpl, lo, hi)
            rnd = np.arange(hi - lo) // round_rows
            if keep is not None:
                rnd = rnd[keep]
            n_rnd = -(-(hi - lo) // round_rows)
            idx = rnd * G + code
            cnt = np.bincount(idx, minlength=n_rnd * G).reshape(n_rnd, G)
            dsum = np.bincount(idx, weights=val.astype(np.float64) - center,
                               minlength=n_rnd * G).reshape(n_rnd, G)
            return cnt.astype(np.float32), dsum.astype(np.float32)

        run_n = np.zeros(G, np.float32)
        run_s = np.zeros(G, np.float32)
        for cnt, dsum in self._map(part, CHUNK_ROWS - CHUNK_ROWS % round_rows):
            for r in range(cnt.shape[0]):
                run_n += cnt[r]
                run_s += dsum[r]
        mean = np.float32(center) + run_s / np.maximum(run_n, np.float32(1))
        return run_n.astype(np.float64), mean.astype(np.float64)
