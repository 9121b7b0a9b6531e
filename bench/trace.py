"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: the device's operations, its busy time (the
union of their intervals), time by operation name, and idle gaps
labelled with the host span that was open during them.

On a TPU the operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  On the CPU backend (tests) they are the
events of the host plane that carry an ``hlo_op`` statistic.
"""
from __future__ import annotations

import pathlib
import re
import warnings
from typing import NamedTuple

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """The op's own name: a TPU trace names an op by its whole HLO
        text (``%fusion.253 = (f32[...]) fusion(...)``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    def text(self) -> str:
        """The name and every statistic's value, for name matching."""
        return " ".join([self.name, *(str(v) for _k, v in self.stats)])


def load(trace_dir) -> object:
    """The newest ``.xplane.pb`` written under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _events(line, keep_stats: bool) -> list:
    out = []
    with warnings.catch_warnings():
        # the profiler's stats type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for e in line.events:
            stats = tuple(e.stats) if keep_stats else ()
            out.append(Event(e.name, float(e.start_ns), float(e.duration_ns),
                             stats))
    return out


def device_ops(prof, plane_prefix: str = TPU_PLANE,
               line_name: str | None = OPS_LINE) -> dict:
    """{plane name: [Event]} of the device operations, sorted by start.

    ``line_name=None`` takes, from every line of the matching planes, the
    events that carry an ``hlo_op`` statistic (the CPU backend's layout).
    """
    out = {}
    for plane in prof.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        evs = []
        for line in plane.lines:
            if line_name is not None:
                if line.name == line_name:
                    evs.extend(_events(line, keep_stats=True))
            else:
                evs.extend(e for e in _events(line, keep_stats=True)
                           if any(k == "hlo_op" for k, _v in e.stats))
        if evs:
            out[plane.name] = sorted(evs, key=lambda e: e.start_ns)
    return out


def host_spans(prof, names) -> list:
    """Host events whose name is one of ``names`` (the harness's own
    ``TraceAnnotation`` spans), sorted by start."""
    names = set(names)
    evs = []
    for plane in prof.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            evs.extend(e for e in _events(line, keep_stats=False)
                       if e.name in names)
    return sorted(evs, key=lambda e: e.start_ns)


def intervals(events) -> list:
    """Merged [start, end) intervals (ns) covered by ``events``."""
    merged: list = []
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = e.start_ns, e.end_ns
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return merged


def busy_s(events) -> float:
    """Seconds in which at least one of ``events`` ran."""
    return sum(t - s for s, t in intervals(events)) * 1e-9


def gaps(events, min_ns: float = 0.0) -> list:
    """Idle [start, end) gaps (ns) between the merged intervals."""
    iv = intervals(events)
    return [(a[1], b[0]) for a, b in zip(iv, iv[1:]) if b[0] - a[1] > min_ns]


def matching(events, pattern: str) -> list:
    """Events whose name or statistics match the regular expression."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.text())]


def of_kind(events, kinds) -> list:
    """Events whose HLO op kind is one of ``kinds``, asynchronous start
    and done halves included.  The kind is read from the op's HLO text
    (``%psum.91 = f32[8] all-reduce(f32[8] %x)``: the name is the JAX
    primitive's, the kind follows the type), not from an operand's name."""
    rx = re.compile(r"\s(?:%s)(?:-start|-done)?\(" % "|".join(map(re.escape, kinds)))
    return [e for e in events if rx.search(e.text())]


def seconds_by_name(events) -> dict:
    out: dict = {}
    for e in events:
        out[e.short] = out.get(e.short, 0.0) + e.dur_ns * 1e-9
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gap_list, spans) -> dict:
    """{host span name: idle seconds} — each gap goes to the host span
    that overlaps it most ("unattributed" where none does)."""
    out: dict = {}
    for s, t in gap_list:
        best, best_ov = "unattributed", 0.0
        for sp in spans:
            if sp.start_ns >= t:
                break
            ov = min(t, sp.end_ns) - max(s, sp.start_ns)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out[best] = out.get(best, 0.0) + (t - s) * 1e-9
    return out


class Summary(NamedTuple):
    """What the per-layer readers and the result line take from a trace."""
    ops: dict              # {device plane: [Event]}
    busy_s: float          # union of op intervals, averaged over planes
    window_s: float        # length of the traced window (host clock)
    device_ops: list       # [[name, seconds]] top 10, summed over planes
    idle_gaps: list        # [[host span, seconds]] top 10, averaged

    def all_ops(self) -> list:
        return [e for evs in self.ops.values() for e in evs]


def summarize(prof, window_s: float, span_names=(),
              plane_prefix: str = TPU_PLANE,
              line_name: str | None = OPS_LINE) -> Summary:
    ops = device_ops(prof, plane_prefix, line_name)
    if not ops:
        raise ValueError(f"the trace holds no device operations under "
                         f"{plane_prefix!r}")
    n = len(ops)
    busy = sum(busy_s(evs) for evs in ops.values()) / n
    by_name: dict = {}
    idle: dict = {}
    spans = host_spans(prof, span_names) if span_names else []
    for evs in ops.values():
        for k, v in seconds_by_name(evs).items():
            by_name[k] = by_name.get(k, 0.0) + v
        for k, v in label_gaps(gaps(evs), spans).items():
            idle[k] = idle.get(k, 0.0) + v / n
    return Summary(ops, busy, window_s, top(by_name), top(idle))
