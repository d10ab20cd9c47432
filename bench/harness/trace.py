"""Reduce a profiler trace to device busy time, kernel and program time,
and a breakdown.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
writes into a flat list of :class:`Ev`; everything else works on that
list, so a test can hand it a recorded or a hand-made one.

Device planes are ``/device:TPU:<n>``; on them the ``XLA Ops`` line
holds one event per executed operation (a Pallas kernel under its
kernel name) and ``XLA Modules`` one per executed program.  Host spans
the benchmark opens with ``jax.profiler.TraceAnnotation`` are events
named ``bench.*`` on host planes.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN = "bench."


@dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(log_dir: str) -> List[Ev]:
    """The device operations and programs, and the benchmark's host
    spans, of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {log_dir}")
    pd = ProfileData.from_file(paths[0])
    out: List[Ev] = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith(HOST_SPAN):
                    continue
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns)))
    return out


def device_planes(evs: Iterable[Ev]) -> List[str]:
    names = {e.plane for e in evs if DEVICE_PLANE.match(e.plane)}
    return sorted(names, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def ops(evs: Sequence[Ev], plane: str) -> List[Ev]:
    return [e for e in evs if e.plane == plane and e.line == OPS_LINE]


def modules(evs: Sequence[Ev], plane: str) -> List[Ev]:
    return [e for e in evs if e.plane == plane and e.line == MODULES_LINE]


def host_spans(evs: Sequence[Ev]) -> List[Ev]:
    return [e for e in evs if e.name.startswith(HOST_SPAN)]


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(evs: Sequence[Ev], plane: str, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi)`` in which some operation ran."""
    return total(clip(union((e.start_ns, e.end_ns) for e in ops(evs, plane)),
                      lo, hi))


def _gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


WRAPPERS = ("%while", "%conditional", "%call")
OP_TEXT = re.compile(r"^%?(\S+) = (\(?[\w\[\],]+?)(?:\{[^}]*\})? ([\w-]+)\(")


def short(name: str) -> str:
    """``%copy.28 = bf16[24,65]{...} copy(...)`` -> ``copy.28: copy
    bf16[24,65]`` (the op, its kind and its result type)."""
    m = OP_TEXT.match(name)
    if m is None:
        return name[:100]
    return f"{m.group(1)}: {m.group(3)} {m.group(2)}"


def breakdown(evs: Sequence[Ev], plane: str, lo: float, hi: float,
              k: int = 10) -> Dict[str, list]:
    """The ``k`` operations that took most device time (loops and calls,
    which only enclose other operations, left out), and the ``k``
    longest idle gaps of ``[lo, hi)``, each named by the host span that
    overlaps it most (``host.outside_spans`` where none does)."""
    per: Dict[str, float] = defaultdict(float)
    for e in ops(evs, plane):
        if e.end_ns > lo and e.start_ns < hi and \
                not e.name.startswith(WRAPPERS):
            per[short(e.name)] += e.dur_ns
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    busy = clip(union((e.start_ns, e.end_ns) for e in ops(evs, plane)),
                lo, hi)
    spans = host_spans(evs)
    gaps = sorted(_gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:k]
    named = []
    for s, e in gaps:
        best, over = "host.outside_spans", 0.0
        for h in spans:
            o = min(e, h.end_ns) - max(s, h.start_ns)
            if o > over:
                best, over = h.name, o
        named.append([best, (e - s) * 1e-9])
    return {"device_ops": [[n, v * 1e-9] for n, v in top],
            "idle_gaps": named}


@dataclass
class Reduced:
    """What the per-layer readers take from a trace (seconds)."""
    window_s: float
    busy_s: float                      # averaged over the chips used
    breakdown: Dict[str, list]
    chips: int
    op_events: List[List[Ev]]          # per chip, inside the window
    module_events: List[List[Ev]]      # per chip, inside the window

    def ops(self, pattern: str) -> Tuple[float, int]:
        """Seconds and calls of the operations whose name holds
        ``pattern``, summed over the chips used."""
        hits = [e for chip in self.op_events for e in chip
                if pattern in e.name]
        return sum(e.dur_ns for e in hits) * 1e-9, len(hits)

    def programs_with(self, op_pattern: str) -> Tuple[float, int]:
        """Seconds and runs, summed over the chips used, of the programs
        that ran an operation whose name holds ``op_pattern`` (a program
        is found by what it runs, not by its module name, which the
        compiler may leave as ``jit__unknown``)."""
        t, n = 0.0, 0
        for mods, chip_ops in zip(self.module_events, self.op_events):
            starts = sorted(e.start_ns for e in chip_ops
                            if op_pattern in e.name)
            for m in mods:
                i = bisect.bisect_left(starts, m.start_ns)
                if i < len(starts) and starts[i] < m.end_ns:
                    t += m.dur_ns
                    n += 1
        return t * 1e-9, n


def reduce(evs: Sequence[Ev], lo_ns: float, hi_ns: float, chips: int
           ) -> Reduced:
    """Reduce the events of the window ``[lo_ns, hi_ns)`` on the first
    ``chips`` device planes."""
    planes = device_planes(evs)[:chips]
    if len(planes) < chips:
        raise ValueError(f"trace holds {len(planes)} device planes, "
                         f"{chips} expected")
    inside = [e for e in evs if e.end_ns > lo_ns and e.start_ns < hi_ns]
    busy = [busy_ns(inside, p, lo_ns, hi_ns) for p in planes]
    return Reduced(window_s=(hi_ns - lo_ns) * 1e-9,
                   busy_s=sum(busy) / len(planes) * 1e-9,
                   breakdown=breakdown(inside, planes[0], lo_ns, hi_ns),
                   chips=len(planes),
                   op_events=[ops(inside, p) for p in planes],
                   module_events=[modules(inside, p) for p in planes])


def window_bounds(evs: Sequence[Ev], open_name: str, close_name: str
                  ) -> Tuple[float, float]:
    """The measured window: from the start of the host mark
    ``open_name`` to the start of ``close_name``."""
    t = {}
    for e in evs:
        if e.name in (open_name, close_name) and e.name not in t:
            t[e.name] = e.start_ns
    if len(t) != 2:
        raise ValueError(f"trace lacks the window marks: found {sorted(t)}")
    return t[open_name], t[close_name]
