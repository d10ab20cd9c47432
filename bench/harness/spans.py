"""Read the serving program's own spans: where the host's time goes
inside ``step()``, and which host work the device's idle time sits in.

The program opens its spans (``lpu.*``, and ``py.gc`` for the garbage
collector's pauses) only when a ``repro.serving.tracker.SpanRecorder``
is attached; each span is also a ``jax.profiler.TraceAnnotation``, so a
traced run finds them in the profiler's trace on the device trace's
clock, their counts as event stats.  :func:`load` reads that trace in
one pass: the events :func:`harness.trace.load_xplane` reads, unchanged
(so the existing reduction reads the same numbers), and the program's
spans beside them.  A request's own spans (``lpu.request.*``) live in
the recorder's memory only; :func:`queue_wait_ms_p90` reads them there,
on the host clock the benchmark's window marks use.

Every reading here relates spans to spans, or spans to device events of
the same trace, so the host's and the profiler's clocks are never mixed.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from harness import trace as T

PROGRAM_SPANS = ("lpu.", "py.")
WAIT = "lpu.wait"
OUTSIDE = "outside_spans"


@dataclass(frozen=True)
class Span:
    """One host span of the trace, with the counts it carries."""
    name: str
    start_ns: float
    dur_ns: float
    counts: Dict[str, float] = field(default_factory=dict, compare=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(log_dir: str) -> Tuple[List[T.Ev], List[Span]]:
    """The events of the one ``.xplane.pb`` under ``log_dir``: those
    :func:`harness.trace.load_xplane` keeps, and the program's spans
    and the benchmark's marks (``bench.*``) as :class:`Span`."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {log_dir}")
    pd = ProfileData.from_file(paths[0])
    evs: List[T.Ev] = []
    spans: List[Span] = []
    for plane in pd.planes:
        device = bool(T.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (T.OPS_LINE, T.MODULES_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(T.HOST_SPAN):
                    evs.append(T.Ev(plane.name, line.name, e.name,
                                    float(e.start_ns),
                                    float(e.duration_ns)))
                if not device and e.name.startswith(
                        PROGRAM_SPANS + (T.HOST_SPAN,)):
                    spans.append(Span(e.name, float(e.start_ns),
                                      float(e.duration_ns),
                                      dict(e.stats)))
    spans.sort(key=lambda s: (s.start_ns, -s.dur_ns))
    return evs, spans


def inside(spans: Sequence[Span], lo: float, hi: float,
           name: Optional[str] = None) -> List[Span]:
    """The spans (named ``name``) that lie wholly in ``[lo, hi)``."""
    return [s for s in spans if s.start_ns >= lo and s.end_ns <= hi
            and (name is None or s.name == name)]


# -- readings ----------------------------------------------------------------

def prefill_ms(spans: Sequence[Span], lo: float, hi: float
               ) -> Optional[float]:
    """Mean over the prompts prefilled in the window of their summed
    ``lpu.prefill`` time (a chunked prompt has several), in ms."""
    per: Dict[int, float] = defaultdict(float)
    for s in inside(spans, lo, hi, "lpu.prefill"):
        per[int(s.counts["rid"])] += s.dur_ns
    if not per:
        return None
    return sum(per.values()) / len(per) * 1e-6


def host_ms_per_decode_step(spans: Sequence[Span], lo: float, hi: float
                            ) -> Optional[float]:
    """Host time per decode step of the window's ``lpu.step`` spans
    that ran no prefill: their time less the ``lpu.wait`` time inside
    them (the host blocked on the device), over the decode steps they
    reconciled, in ms."""
    steps = [s for s in inside(spans, lo, hi, "lpu.step")
             if s.counts.get("prefills", 0) == 0
             and s.counts.get("steps", 0) > 0]
    if not steps:
        return None
    waits = inside(spans, lo, hi, WAIT)
    starts = [w.start_ns for w in waits]
    t = n = 0.0
    for s in steps:
        i = bisect.bisect_left(starts, s.start_ns)
        waited = 0.0
        while i < len(waits) and waits[i].start_ns < s.end_ns:
            waited += min(waits[i].end_ns, s.end_ns) - waits[i].start_ns
            i += 1
        t += s.dur_ns - waited
        n += s.counts["steps"]
    return t / n * 1e-6


def idle_gaps(ops: Sequence[T.Ev], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi)`` in which no operation ran."""
    return T._gaps(T.clip(T.union((e.start_ns, e.end_ns) for e in ops),
                          lo, hi), lo, hi)


def _measure(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_host_share(spans: Sequence[Span], ops: Sequence[T.Ev],
                       lo: float, hi: float) -> Optional[float]:
    """Share of the window, in %, in which the device ran no operation
    while the host was inside a program span (``lpu.*``) and not
    blocked on the device (``lpu.wait``): the idle the host's own work
    causes."""
    if hi <= lo:
        return None
    prog = [s for s in spans if s.name.startswith("lpu.")]
    if not prog:
        return None
    host = T.clip(T.union((s.start_ns, s.end_ns) for s in prog
                          if s.name != WAIT), lo, hi)
    waits = T.clip(T.union((s.start_ns, s.end_ns) for s in prog
                           if s.name == WAIT), lo, hi)
    idle = idle_gaps(ops, lo, hi)
    # every lpu.wait lies inside an lpu.step, so the host's own work is
    # its spans' time less the waits in them
    return 100.0 * (_measure(idle, host) - _measure(idle, waits)) / (hi - lo)


def split_by_innermost(spans: Sequence[Span],
                       intervals: Sequence[Tuple[float, float]]
                       ) -> Dict[str, float]:
    """Seconds of ``intervals`` (sorted, disjoint) under each span name,
    each instant going to the innermost span open over it (the one that
    opened last: spans of one thread nest), or to ``outside_spans``."""
    if not intervals:
        return {}
    marks = []
    for k, s in enumerate(spans):
        marks.append((s.start_ns, 1, k))
        marks.append((s.end_ns, 0, k))
    marks.sort()
    starts = [a for a, _ in intervals]
    cum = np.concatenate([[0.0], np.cumsum([b - a for a, b in intervals])])

    def covered(t: float) -> float:
        """Length of ``intervals`` before ``t``."""
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        a, b = intervals[i]
        return cum[i] + min(t, b) - a

    out: Dict[str, float] = defaultdict(float)
    stack: List[int] = []
    t = intervals[0][0]
    for when, opening, k in marks:
        if when > t:
            name = spans[stack[-1]].name if stack else OUTSIDE
            out[name] += covered(when) - covered(t)
            t = when
        if opening:
            stack.append(k)
        elif k in stack:
            stack.remove(k)
    end = intervals[-1][1]
    if end > t:
        name = spans[stack[-1]].name if stack else OUTSIDE
        out[name] += covered(end) - covered(t)
    return {k: v * 1e-9 for k, v in sorted(out.items(),
                                           key=lambda kv: -kv[1]) if v > 0}


def stalls(spans: Sequence[Span], ops: Sequence[T.Ev], lo: float,
           hi: float, factor: float = 2.0) -> List[dict]:
    """The window's decode-only ``lpu.step`` spans longer than
    ``factor`` times their median: each with its time split by the
    innermost span over it, and the device's idle time inside it.  A
    stall inside ``lpu.wait`` is the device or its runtime standing
    still; inside another span, or ``py.gc``, the host."""
    steps = [s for s in inside(spans, lo, hi, "lpu.step")
             if s.counts.get("prefills", 0) == 0]
    if len(steps) < 2:
        return []
    med = float(np.median([s.dur_ns for s in steps]))
    out = []
    for s in steps:
        if s.dur_ns <= factor * med:
            continue
        iv = [(s.start_ns, s.end_ns)]
        out.append({"ms": s.dur_ns * 1e-6, "median_ms": med * 1e-6,
                    "at_s": (s.start_ns - lo) * 1e-9,
                    "split_s": split_by_innermost(spans, iv),
                    "device_idle_s": _measure(idle_gaps(ops, *iv[0]),
                                              iv) * 1e-9})
    return out


def queue_wait_ms_p90(requests: Dict[int, list], t0: float, t1: float
                      ) -> Optional[float]:
    """90th percentile, over the requests that entered the queue in the
    window ``[t0, t1)`` (host clock), of each one's summed
    ``lpu.request.queue`` time, in ms.  ``requests`` is the recorder's
    in-memory ``SpanRecorder.requests``."""
    waits = []
    for spans in requests.values():
        queue = [s for s in spans if s.name == "lpu.request.queue"]
        if queue and t0 <= queue[0].start < t1:
            waits.append(sum(s.end - s.start for s in queue) * 1e3)
    if not waits:
        return None
    return float(np.percentile(waits, 90))
