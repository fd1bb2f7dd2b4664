"""The program's own spans, read against the device trace.

``serve/engine.py`` opens ``lb.*`` spans (``jax.profiler.TraceAnnotation``)
where its read path works: ``lb.serve_window`` around a ``get_many`` call;
``lb.lookup`` per request, with ``lb.fetch`` inside it on a full miss;
``lb.flush`` around the batched decode, holding per chunk ``lb.assemble``
(``lb.decompress``, ``lb.warm_up``, ``lb.place``), ``lb.dispatch`` and
``lb.collect``; then ``lb.writeback``.  Spans of one call share the
``call`` stat, those of one request its ``oid``; parentage is nesting on
the one serving thread.

The benchmark's reduction (``lib/profile.py``) keeps only the benchmark's
own spans.  :func:`reduce` reads the same trace once more and returns a
:class:`SpanTrace`: that reduction's summary with every host event named
``lb.*`` beside it, with its stats, on the device's clock.

* :func:`join`: each ``lb.lookup`` with the ``lb.serve_window`` of its call;
* :func:`partition`: the window cut at every span edge, each piece named
  by the innermost span over it: a program span, else the benchmark's own
  (``get_many``, ``await_arrival``), else ``other``;
* :func:`idle_by_span`: the device's idle seconds in the window by that
  name; :func:`totals`: count, total and self milliseconds per span name
  (self time is the part of a span no child span covers);
* :func:`codec_ms`, :func:`hit_held_ms` and :func:`prep_idle_ms`: the
  codec's median decompression, the median hold of a ready pixel hit, and
  the device's idle inside chunk assembly per call.  The benchmark does
  not report them: its result line is built from ``lib/profile.py``'s
  summary, which has no program spans.

A trace of a program that records no such spans reads as no program spans:
the three functions return None and the idle falls to the benchmark's spans.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from lib import profile
from lib.profile import Op, TraceSummary, clip, merge

PREFIX = "lb."
OTHER = "other"
IMAGE_HIT = "image_hit"


@dataclasses.dataclass
class ProgramSpan(Op):
    """A span the program recorded, with its stats (``call``, ``oid``...)."""
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SpanTrace(TraceSummary):
    """The benchmark's reduction of a trace, with the program's spans."""
    program_spans: List[ProgramSpan] = dataclasses.field(default_factory=list)


def program_spans(pd) -> List[ProgramSpan]:
    """Every host event of ``pd`` (a ``jax.profiler.ProfileData``) whose
    name starts with ``lb.``."""
    return [ProgramSpan(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def reduce(pd, **kw) -> Optional[SpanTrace]:
    """``profile.reduce(pd, **kw)`` with the program's spans kept."""
    summary = profile.reduce(pd, **kw)
    if summary is None:
        return None
    fields = {f.name: getattr(summary, f.name)
              for f in dataclasses.fields(TraceSummary)}
    return SpanTrace(**fields, program_spans=program_spans(pd))


def reduce_dir(log_dir: str, **kw) -> Optional[SpanTrace]:
    """:func:`reduce` of the newest trace ``jax.profiler`` wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return None
    return reduce(ProfileData.from_file(sorted(paths)[-1]), **kw)


def in_window(trace: SpanTrace, name: str) -> List[ProgramSpan]:
    """The program spans called ``name`` that start inside the window."""
    return [s for s in trace.program_spans
            if s.name == name and trace.t0_ns <= s.start_ns < trace.t1_ns]


def join(trace: SpanTrace) -> List[Tuple[ProgramSpan, ProgramSpan]]:
    """(lookup, serve_window) pairs: each ``lb.lookup`` in the window with
    the ``lb.serve_window`` of the same ``call``."""
    calls = {s.stats.get("call"): s for s in in_window(trace,
                                                        "lb.serve_window")}
    return [(s, calls[s.stats.get("call")])
            for s in in_window(trace, "lb.lookup")
            if s.stats.get("call") in calls]


def partition(trace: SpanTrace) -> List[Tuple[float, float, str]]:
    """(start_ns, end_ns, name) pieces that tile the window, cut at every
    span edge, each named by the innermost span over it: program spans
    before the benchmark's, the later start within each."""
    t0, t1 = trace.t0_ns, trace.t1_ns
    spans = ([(1, s.start_ns, s.end_ns, s.name)
              for s in trace.program_spans]
             + [(0, s, e, name) for name, s, e in trace.spans])
    spans = sorted((x for x in spans if x[2] > t0 and x[1] < t1),
                   key=lambda x: x[1])
    edges = sorted({t0, t1} | {t for x in spans for t in x[1:3]
                               if t0 < t < t1})
    out: List[Tuple[float, float, str]] = []
    active: List[Tuple[int, float, float, str]] = []
    k = 0
    for a, b in zip(edges, edges[1:]):
        mid = 0.5 * (a + b)
        while k < len(spans) and spans[k][1] <= mid:
            active.append(spans[k])
            k += 1
        active = [x for x in active if x[2] >= mid]
        top = max(active, key=lambda x: (x[0], x[1], -x[2]), default=None)
        out.append((a, b, top[3] if top else OTHER))
    return out


def idle_ns(trace: TraceSummary, chip: int = 0
            ) -> Callable[[float, float], float]:
    """``idle(a, b)``: nanoseconds of [a, b] in which the chip ran no
    operation (the complement of the reduction's busy union)."""
    busy = trace.busy_intervals(chip)
    starts = [s for s, _ in busy]
    done = [0.0]
    for s, e in busy:
        done.append(done[-1] + e - s)

    def busy_to(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = busy[i - 1]
        return done[i - 1] + min(e, t) - s

    return lambda a, b: (b - a) - (busy_to(b) - busy_to(a))


def idle_by_span(trace: SpanTrace, chip: int = 0) -> Dict[str, float]:
    """Idle seconds of the window by the innermost span over each idle
    instant; the values sum to the window's idle time on ``chip``."""
    idle = idle_ns(trace, chip)
    out: Dict[str, float] = {}
    for a, b, name in partition(trace):
        v = idle(a, b)
        if v > 0:
            out[name] = out.get(name, 0.0) + v * 1e-9
    return out


def totals(trace: SpanTrace) -> Dict[str, Tuple[int, float, float]]:
    """(count, total ms, self ms) per program span name in the window."""
    names = sorted({s.name for s in trace.program_spans})
    self_ns = {n: 0.0 for n in names}
    for a, b, name in partition(trace):
        if name in self_ns:
            self_ns[name] += b - a
    out = {}
    for n in names:
        spans = in_window(trace, n)
        total = sum(e - s for s, e in clip(
            [(x.start_ns, x.end_ns) for x in spans], trace.t0_ns,
            trace.t1_ns))
        out[n] = (len(spans), total * 1e-6, self_ns[n] * 1e-6)
    return out


def _median(values) -> Optional[float]:
    return float(np.median(values)) if len(values) else None


def codec_ms(trace: SpanTrace) -> Optional[float]:
    """Median duration of the host codec's decompressions in the window
    (``lb.decompress``), in ms."""
    return _median([s.seconds * 1e3 for s in in_window(trace,
                                                       "lb.decompress")])


def hit_held_ms(trace: SpanTrace) -> Optional[float]:
    """Median, over image hits whose pixels were in hand at lookup, of the
    end of their call's ``lb.serve_window`` less the end of their
    ``lb.lookup``: how long a ready pixel hit waits for its call's
    decodes, in ms."""
    return _median([(w.end_ns - s.end_ns) * 1e-6 for s, w in join(trace)
                    if s.stats.get("cls") == IMAGE_HIT
                    and s.stats.get("ready") == 1])


def prep_idle_ms(trace: SpanTrace) -> Optional[float]:
    """Device-idle time inside ``lb.assemble`` spans (decompression,
    stacking, host-to-device placement of a chunk) per ``lb.serve_window``
    call, in ms."""
    calls = len(in_window(trace, "lb.serve_window"))
    assemble = merge(clip([(s.start_ns, s.end_ns)
                           for s in trace.program_spans
                           if s.name == "lb.assemble"], trace.t0_ns,
                          trace.t1_ns))
    if not calls or not assemble:
        return None
    idle = idle_ns(trace)
    return sum(idle(a, b) for a, b in assemble) * 1e-6 / calls
