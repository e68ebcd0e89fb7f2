"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run on the chip. Host planes hold the benchmark's own spans,
``jax.profiler.TraceAnnotation`` events whose names start with
``bench:``, on the same clock.

* busy: the union of a device's operation intervals inside a window,
  averaged over the devices;
* idle gaps: the stretches of the window with no operation on the
  device, each labelled with the innermost benchmark span around it;
* operation totals: device seconds per operation name.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

SPAN_PREFIX = "bench:"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10
# operations whose event spans the operations of their body, which the
# trace records as events of their own
CONTAINERS = ("while", "conditional", "call")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(text: str) -> str:
    """``'<instruction> <opcode>'`` from the HLO text an ``XLA Ops`` event
    is named by, e.g. ``'block_agg.14 custom-call'``; a custom call other
    than a Pallas kernel also names its target."""
    lhs, eq, rhs = text.partition(" = ")
    if not eq:
        return text[:120]
    rest = rhs
    if rest.startswith("("):           # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.partition("(")[0]
    label = f"{lhs.lstrip('%')} {opcode}"
    target = _TARGET.search(rhs)
    if target and target.group(1) != "tpu_custom_call":
        label += f" {target.group(1)}"
    return label


def options():
    """Profiler options for a benchmark trace: device operations and the
    benchmark's own spans; no Python function tracing, no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@dataclasses.dataclass
class Trace:
    """Device operation intervals (per device, ns) and benchmark spans."""

    ops: list       # per device: (starts, ends, label ids) arrays
    labels: list    # label of each id (``op_label``)
    spans: list     # (name, start_ns, end_ns)

    def span(self, name: str):
        """``(start_ns, end_ns)`` of the span named ``name``."""
        for n, s, e in self.spans:
            if n == name:
                return s, e
        raise KeyError(name)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, spans, ids, labels = [], [], {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                starts, ends, lab = [], [], []
                for e in line.events:
                    starts.append(e.start_ns)
                    ends.append(e.end_ns)
                    name = e.name
                    if name not in ids:
                        ids[name] = len(labels)
                        labels.append(op_label(name))
                    lab.append(ids[name])
                ops.append((np.array(starts, np.float64),
                            np.array(ends, np.float64),
                            np.array(lab, np.int64)))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    return Trace(ops=ops, labels=labels, spans=spans)


def union(starts, ends, a: float, b: float) -> np.ndarray:
    """Merged ``(k, 2)`` busy intervals, clipped to ``[a, b]``."""
    s = np.clip(np.asarray(starts, np.float64), a, b)
    e = np.clip(np.asarray(ends, np.float64), a, b)
    keep = e > s
    s, e = s[keep], e[keep]
    if not s.size:
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    first = np.nonzero(new)[0]
    last = np.append(first[1:] - 1, s.size - 1)
    return np.stack([s[first], e[last]], axis=1)


def busy_ns(merged: np.ndarray, a: float, b: float) -> float:
    """Busy nanoseconds of ``merged`` inside ``[a, b]``."""
    lo = np.maximum(merged[:, 0], a)
    hi = np.minimum(merged[:, 1], b)
    return float(np.clip(hi - lo, 0, None).sum())


def idle(merged: np.ndarray, a: float, b: float) -> np.ndarray:
    """Idle ``(k, 2)`` intervals of ``[a, b]`` between busy ones."""
    edges = np.concatenate([[a], merged.reshape(-1), [b]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def label(spans: list, t: float) -> str:
    """Name of the shortest benchmark span containing ``t``."""
    best, width = "no benchmark span", np.inf
    for name, s, e in spans:
        if s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


class Window:
    """The device numbers of one traced window ``[a, b]`` (ns)."""

    def __init__(self, trace: Trace, a: float, b: float):
        if not trace.ops:
            raise ValueError("the trace holds no device operations")
        self.trace = trace
        self.a, self.b = float(a), float(b)
        self.merged = [union(s, e, self.a, self.b) for s, e, _ in trace.ops]

    @property
    def window_s(self) -> float:
        return (self.b - self.a) * 1e-9

    def busy_s(self, a: float = None, b: float = None) -> float:
        """Device-busy seconds in ``[a, b]`` (default: the window),
        averaged over the devices."""
        a = self.a if a is None else max(a, self.a)
        b = self.b if b is None else min(b, self.b)
        return float(np.mean([busy_ns(m, a, b) for m in self.merged])) * 1e-9

    def device_ops(self, top: int = TOP) -> list:
        """``[[operation, seconds], ...]``: device time per operation in
        the window, largest first, averaged over the devices; a
        ``while``, ``conditional`` or ``call`` is left out, since the
        operations of its body are counted."""
        labels = self.trace.labels
        tot = np.zeros(len(labels))
        for s, e, lab in self.trace.ops:
            d = np.clip(np.minimum(e, self.b) - np.maximum(s, self.a), 0,
                        None)
            tot += np.bincount(lab, weights=d, minlength=len(labels))
        tot *= 1e-9 / len(self.trace.ops)
        keep = [i for i in np.argsort(-tot, kind="stable")
                if tot[i] > 0 and labels[i].split(" ")[1] not in CONTAINERS]
        return [[labels[i], float(tot[i])] for i in keep[:top]]

    def idle_gaps(self, top: int = TOP) -> list:
        """``[[span, seconds], ...]``: the longest idle gaps of the first
        device, each named by the benchmark span the host was in."""
        gaps = idle(self.merged[0], self.a, self.b)
        order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:top]
        return [[label(self.trace.spans, 0.5 * (gaps[i, 0] + gaps[i, 1])),
                 float(gaps[i, 1] - gaps[i, 0]) * 1e-9] for i in order]
