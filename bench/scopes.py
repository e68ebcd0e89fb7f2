"""The program's own spans and named scopes in a profiler trace.

``bench/tracing.py`` reduces a trace to the benchmark's device numbers
from the ``bench:`` spans and the device operations. The program records
more on the same clock. ``FastFrame.run`` annotates its host phases
with ``jax.profiler.TraceAnnotation`` spans whose names start with
``aqp:`` (``aqp:run`` and, inside it, ``PHASES``). The round loop's
operations carry ``jax.named_scope`` names (``SCOPES``) in the
``op_name`` of their HLO instructions. This module reads both:

* ``options()``: the benchmark's profiler options with HLO protos on, so
  that the ``/host:metadata`` plane holds each program's optimized HLO;
* ``load(path)``: a ``tracing.Trace`` that also holds the ``aqp:`` spans
  and each device operation's scope. An operation's program is the
  ``XLA Modules`` event it falls in, its instruction the left-hand side
  of its label, and its scope the innermost of ``SCOPES`` in that
  instruction's ``op_name``;
* ``Window``: a ``tracing.Window`` whose idle gaps are labelled with the
  innermost span of either kind, with device time per scope;
* ``numbers(window, traced)``: the per-layer numbers of ``METRICS``,
  and ``report(window, traced)``: log lines that break the window down.

``bench/scope_report.py`` runs a cell's traced run with this module.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from bench import tracing

AQP_PREFIX = "aqp:"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# the children of ``aqp:run`` on the device-loop path, in order
PHASES = ("aqp:views", "aqp:upload", "aqp:loop", "aqp:writeback",
          "aqp:recovery", "aqp:result")
# the round loop's named scopes (``repro.kernels.fused_scan.SCOPES``)
SCOPES = ("select", "gather", "fold", "merge", "account", "refresh")
# per-layer number: the program spans (``*_ms_per_answer``) or scopes
# (``*_us_per_round``) it sums
METRICS = {
    "prepare_ms_per_answer": ("aqp:views", "aqp:upload"),
    "finish_ms_per_answer": ("aqp:writeback", "aqp:recovery", "aqp:result"),
    "select_us_per_round": ("select",),
    "fold_us_per_round": ("gather", "fold", "merge"),
    "account_us_per_round": ("account",),
    "refresh_us_per_round": ("refresh",),
}


def options():
    """``tracing.options()`` with HLO protos on."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = True
    return opts


@dataclasses.dataclass
class Trace(tracing.Trace):
    """A ``tracing.Trace`` with the program's spans and scopes."""

    aqp_spans: list = dataclasses.field(default_factory=list)
    # (name, start_ns, end_ns) of the program's ``aqp:`` spans
    op_scopes: list = dataclasses.field(default_factory=list)
    # per device: index into SCOPES of each operation, -1 for none; empty
    # where the trace holds no HLO protos

    def aqp_s(self, name: str, a: float, b: float) -> float:
        """Seconds of the program spans named ``name`` that start inside
        ``[a, b]``."""
        return sum(e - s for n, s, e in self.aqp_spans
                   if n == name and a <= s <= b) * 1e-9


def load(path) -> Trace:
    """What ``tracing.load`` reads, and the program's spans and scopes."""
    from jax.profiler import ProfileData

    base = tracing.load(path)
    aqp, modules = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        lines = list(plane.lines)
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            mods = [e for line in lines if line.name == MODULES_LINE
                    for e in line.events]
            # one entry per ``XLA Ops`` line, as ``tracing.load`` has
            modules += [([e.start_ns for e in mods], [e.end_ns for e in mods],
                         [e.name for e in mods])
                        for line in lines if line.name == tracing.OPS_LINE]
        else:
            aqp += [(e.name, e.start_ns, e.end_ns) for line in lines
                    for e in line.events if e.name.startswith(AQP_PREFIX)]
    op_names = hlo_op_names(path)
    op_scopes = []
    if op_names:
        op_scopes = [scope_ids(o, m, base.labels, op_names)
                     for o, m in zip(base.ops, modules)]
    return Trace(ops=base.ops, labels=base.labels, spans=base.spans,
                 aqp_spans=aqp, op_scopes=op_scopes)


def scope_of(op_name: str) -> int:
    """Index into ``SCOPES`` of the innermost named scope in an HLO
    ``op_name`` (``'jit(f)/while/body/select/gather'`` -> ``select``), or
    -1. The last component names the primitive, not a scope."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in SCOPES:
            return SCOPES.index(part)
    return -1


def scope_ids(ops: tuple, modules: tuple, labels: list,
              op_names: dict) -> np.ndarray:
    """Scope index of each operation of one device: its program is the
    ``XLA Modules`` event it falls in, its instruction the left-hand side
    of its label; ``op_names[program][instruction]`` is the HLO op_name."""
    starts, _, lab = ops
    m_start = np.asarray(modules[0], np.float64)
    m_end = np.asarray(modules[1], np.float64)
    out = np.full(starts.size, -1, np.int64)
    if not m_start.size:
        return out
    order = np.argsort(m_start, kind="stable")
    m_start, m_end = m_start[order], m_end[order]
    m_names = [modules[2][i] for i in order]
    mod = np.searchsorted(m_start, starts, side="right") - 1
    inside = (mod >= 0) & (starts <= m_end[np.maximum(mod, 0)])
    mod = np.where(inside, mod, -1)
    pairs, inverse = np.unique(np.stack([mod, lab]), axis=1,
                               return_inverse=True)
    found = np.full(pairs.shape[1], -1, np.int64)
    for k, (m, i) in enumerate(pairs.T):
        if m < 0:
            continue
        names = op_names.get(m_names[m], {})
        op = names.get(labels[i].partition(" ")[0])
        if op is not None:
            found[k] = scope_of(op)
    return found[inverse.reshape(-1)]


def _fields(buf, start: int = 0, end: int = None):
    """``(field number, value)`` of each field of the protobuf message in
    ``buf[start:end]``: an int for a varint, ``(start, end)`` for a
    length-delimited field; fixed-width fields are skipped."""
    end = len(buf) if end is None else end
    i = start
    while i < end:
        key = shift = 0
        while True:
            byte = buf[i]
            i += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        kind = key & 7
        if kind in (0, 2):
            val = shift = 0
            while True:
                byte = buf[i]
                i += 1
                val |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            if kind == 2:
                yield key >> 3, (i, i + val)
                i += val
            else:
                yield key >> 3, val
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} is not read")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def module_op_names(buf, start: int = 0, end: int = None) -> dict:
    """``{instruction: op_name}`` of a serialized ``HloModuleProto``
    (computations 3; instruction 2: name 1, metadata 7: op_name 2)."""
    out = {}
    for f, comp in _fields(buf, start, end):
        if f != 3:
            continue
        for f2, inst in _fields(buf, *comp):
            if f2 != 2:
                continue
            name = op = None
            for f3, v in _fields(buf, *inst):
                if f3 == 1:
                    name = _text(buf, v)
                elif f3 == 7:
                    for f4, v4 in _fields(buf, *v):
                        if f4 == 2:
                            op = _text(buf, v4)
            if name is not None and op:
                out[name] = op
    return out


def hlo_op_names(path) -> dict:
    """``{program: {instruction: op_name}}`` from the HLO protos of the
    trace's ``/host:metadata`` plane, keyed as the ``XLA Modules`` events
    are named; empty where the trace holds none. XSpace: planes 1; plane:
    name 2, event metadata 4 (map: key 1, value 2), stat metadata 5;
    event metadata: name 2, stats 5; stat: metadata id 1, bytes 6;
    HloProto: module 1."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        if not any(f2 == 2 and _text(buf, v) == METADATA_PLANE
                   for f2, v in fields):
            continue
        proto_stat = None
        for f2, v in fields:
            if f2 == 5:
                for f3, entry in _fields(buf, *v):
                    if f3 == 2:
                        meta = dict(_fields(buf, *entry))
                        if (2 in meta
                                and _text(buf, meta[2]) == HLO_PROTO_STAT):
                            proto_stat = meta.get(1)
        for f2, v in fields:
            if f2 != 4:
                continue
            for f3, entry in _fields(buf, *v):
                if f3 != 2:
                    continue
                name, protos = None, []
                for f4, v4 in _fields(buf, *entry):
                    if f4 == 2:
                        name = _text(buf, v4)
                    elif f4 == 5:
                        stat = dict(_fields(buf, *v4))
                        if stat.get(1) == proto_stat and 6 in stat:
                            protos.append(stat[6])
                for proto in protos:
                    for f5, mod in _fields(buf, *proto):
                        if f5 == 1:
                            out[name] = module_op_names(buf, *mod)
    return out


def _in_body(labels: list) -> np.ndarray:
    """Per label: whether it is counted as device work, that is, is not
    a loop container whose body's operations are events of their own."""
    return np.array([lab.split(" ")[1] not in tracing.CONTAINERS
                     if " " in lab else True for lab in labels], bool)


class Window(tracing.Window):
    """A ``tracing.Window`` over a ``Trace`` with the program's spans and
    scopes."""

    def idle_gaps(self, top: int = tracing.TOP) -> list:
        """``[[span, seconds], ...]``: the longest idle gaps of the first
        device, each named by the innermost span, the benchmark's or the
        program's, the host was in."""
        gaps = tracing.idle(self.merged[0], self.a, self.b)
        order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:top]
        spans = self.trace.spans + self.trace.aqp_spans
        return [[tracing.label(spans, 0.5 * (gaps[i, 0] + gaps[i, 1])),
                 float(gaps[i, 1] - gaps[i, 0]) * 1e-9] for i in order]

    def _op_seconds(self, s, e) -> np.ndarray:
        return np.clip(np.minimum(e, self.b) - np.maximum(s, self.a), 0,
                       None)

    def scope_s(self) -> dict:
        """``{scope: seconds}``: device time of the operations of each of
        ``SCOPES`` in the window, averaged over the devices; loop
        containers are left out, as in ``device_ops``. Empty where no
        operation in the window carries a scope (a program without
        named scopes, or a trace without HLO protos)."""
        if not self.trace.op_scopes:
            return {}
        body = _in_body(self.trace.labels)
        tot = np.zeros(len(SCOPES))
        for (s, e, lab), sc in zip(self.trace.ops, self.trace.op_scopes):
            keep = (sc >= 0) & body[lab]
            tot += np.bincount(sc[keep], weights=self._op_seconds(s, e)[keep],
                               minlength=len(SCOPES))
        if not tot.any():
            return {}
        tot *= 1e-9 / len(self.trace.ops)
        return dict(zip(SCOPES, map(float, tot)))

    def scoped_ops(self, top: int = tracing.TOP) -> list:
        """``[[operation, scope, seconds], ...]``: the largest operations
        as ``device_ops`` counts them, each with its scope (``None``
        for an operation outside every scope)."""
        if not self.trace.op_scopes:
            return []
        n, k = len(self.trace.labels), len(SCOPES) + 1
        tot = np.zeros(n * k)
        for (s, e, lab), sc in zip(self.trace.ops, self.trace.op_scopes):
            tot += np.bincount(lab * k + sc + 1,
                               weights=self._op_seconds(s, e), minlength=n * k)
        tot *= 1e-9 / len(self.trace.ops)
        body = _in_body(self.trace.labels)
        out = []
        for i in np.argsort(-tot, kind="stable"):
            lab, sc = divmod(int(i), k)
            if tot[i] <= 0 or len(out) == top:
                break
            if body[lab]:
                out.append([self.trace.labels[lab],
                            SCOPES[sc - 1] if sc else None, float(tot[i])])
        return out


def ms_per_answer(window: Window, traced: list, names) -> float | None:
    """Mean over the ``traced`` answers of the milliseconds of the program
    spans ``names`` inside each answer's benchmark span; ``None`` where
    the trace holds none of them."""
    trace = window.trace
    if not traced or not any(n in names for n, _, _ in trace.aqp_spans):
        return None
    total = 0.0
    for a in traced:
        s, e = trace.span(a.span)
        total += sum(trace.aqp_s(n, s, e) for n in names)
    return 1e3 * total / len(traced)


def us_per_round(window: Window, traced: list, scopes) -> float | None:
    """Device microseconds of the named ``scopes`` in the window ÷ the
    rounds the ``traced`` answers report (the denominator of
    ``device_us_per_round``); ``None`` where no operation carries a
    scope."""
    seconds = window.scope_s()
    rounds = sum(a.rounds for a in traced)
    if not seconds or not rounds:
        return None
    return sum(seconds[s] for s in scopes) / rounds * 1e6


def numbers(window: Window, traced: list) -> dict:
    """``{metric: value}`` of ``METRICS``, leaving out those the trace
    gives nothing to read."""
    out = {}
    for name, parts in METRICS.items():
        read = ms_per_answer if name.endswith("_per_answer") else us_per_round
        value = read(window, traced, parts)
        if value is not None:
            out[name] = value
    return out


def report(window: Window, traced: list) -> list:
    """Log lines that break the window down: per traced answer, its
    span, busy and host time and the program's phases (each ``aqp:``
    span's milliseconds, and the device-busy time inside ``aqp:loop``);
    then device seconds per scope with the busy time no scope holds,
    the largest operations with their scopes, and the idle gaps."""
    lines = []
    tr = window.trace
    for a in traced:
        s, e = tr.span(a.span)
        busy = window.busy_s(s, e)
        ph = {n: tr.aqp_s(n, s, e) for n in ("aqp:run",) + PHASES}
        loop_busy = sum(window.busy_s(ls, le) for n, ls, le in tr.aqp_spans
                        if n == "aqp:loop" and s <= ls <= e)
        # prepare + finish + host time inside the loop + outside the run
        parts = (sum(ph[n] for n in PHASES) - loop_busy
                 + (e - s) * 1e-9 - ph["aqp:run"])
        lines.append(
            f"scopes: answer {a.span!r} rounds={a.rounds} "
            f"span_ms={(e - s) * 1e-6} busy_ms={busy * 1e3} "
            f"host_ms={((e - s) * 1e-9 - busy) * 1e3} "
            + " ".join(f"{n[4:]}_ms={v * 1e3}" for n, v in ph.items())
            + f" loop_busy_ms={loop_busy * 1e3} host_parts_ms="
            f"{parts * 1e3}")
    scopes = window.scope_s()
    busy = window.busy_s()
    unscoped = 1.0 - sum(scopes.values()) / busy if scopes and busy else None
    lines.append(f"scopes: scope_s={scopes} busy_s={busy} "
                 f"unscoped_share={unscoped}")
    lines.append(f"scopes: scoped_ops={window.scoped_ops(20)}")
    lines.append(f"scopes: idle_gaps={window.idle_gaps()}")
    return lines
