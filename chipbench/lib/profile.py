"""Reduction of a JAX profiler trace of the measured window to metrics.

The trace holds, per chip, a plane ``/device:TPU:<n>`` whose ``XLA Ops``
line has one event per operation run on the device (named by the HLO
instruction's text) and whose ``XLA Modules`` line has one event per
program execution, and a host plane with the benchmark's own spans
(``window``, ``get_many``, ``await_arrival``).  Device and host events
share one clock.

* busy: the union of the operation intervals inside the window, averaged
  over the chips used; idle is the rest of the window;
* named-event sums: device seconds per operation kind, the HLO
  instruction's name without its numeric suffix (a Pallas kernel shows
  under its op's name, such as ``gn_silu_conv3x3``);
* idle gaps: each stretch of the window with no operation on the device,
  attributed to the host span that covers its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
from typing import Callable, Dict, List, Optional, Sequence, Tuple

HOST_SPANS = ("get_many", "await_arrival")
WINDOW_SPAN = "window"

_OPNAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?: =|$)")
_SHAPE = re.compile(r"\b(f32|bf16|u8|s8|s32|f16)\[([0-9,]*)\]")


@dataclasses.dataclass
class Op:
    name: str           # the event's full name (HLO instruction text)
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def kind_of(name: str) -> str:
    """The HLO instruction's name without its ``.N`` suffix: a Pallas
    kernel's op name (``gn_silu_conv3x3``), or an XLA op's
    (``fusion``, ``copy-start``)."""
    m = _OPNAME.match(name.strip())
    return m.group(1) if m else name[:40]


def shapes_of(name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every array shape in an HLO instruction's text, in order (result
    first, then operands)."""
    return [(t, tuple(int(v) for v in dims.split(",") if v))
            for t, dims in _SHAPE.findall(name)]


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


@dataclasses.dataclass
class TraceSummary:
    t0_ns: float
    t1_ns: float
    ops: List[List[Op]]                 # per chip, operations
    modules: List[List[Op]]             # per chip, program executions
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def busy_intervals(self, chip: int) -> List[Tuple[float, float]]:
        return merge(clip([(o.start_ns, o.end_ns) for o in self.ops[chip]],
                          self.t0_ns, self.t1_ns))

    @property
    def busy_s(self) -> float:
        if not self.ops:
            return 0.0
        total = sum(sum(e - s for s, e in self.busy_intervals(c))
                    for c in range(len(self.ops)))
        return total * 1e-9 / len(self.ops)

    def in_window(self, ops: Sequence[Op]) -> List[Op]:
        return [o for o in ops if o.end_ns > self.t0_ns
                and o.start_ns < self.t1_ns]

    def seconds_by_kind(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for chip_ops in self.ops:
            for o in self.in_window(chip_ops):
                k = kind_of(o.name)
                out[k] = out.get(k, 0.0) + o.seconds
        return out

    def kernel_ops(self, kernels: Sequence[str]) -> List[Op]:
        return [o for chip_ops in self.ops for o in self.in_window(chip_ops)
                if kind_of(o.name) in kernels]

    def module_executions(self, contains: Callable[[Op], bool]
                          ) -> List[Tuple[Op, List[Op]]]:
        """Program executions in the window with their operations, kept
        where some operation satisfies ``contains``."""
        out = []
        for chip_ops, mods in zip(self.ops, self.modules):
            ops = sorted(self.in_window(chip_ops), key=lambda o: o.start_ns)
            starts = [o.start_ns for o in ops]
            for m in self.in_window(mods):
                lo = bisect.bisect_left(starts, m.start_ns)
                hi = bisect.bisect_right(starts, m.end_ns)
                inner = ops[lo:hi]
                if any(contains(o) for o in inner):
                    out.append((m, inner))
        return out

    def idle_gaps(self, chip: int = 0) -> List[Tuple[str, float]]:
        """Idle stretches of the window, longest first, each named by the
        host span around its midpoint ("other" where none is)."""
        busy = self.busy_intervals(chip)
        edges = [self.t0_ns] + [t for iv in busy for t in iv] + [self.t1_ns]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out = []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            host = "other"
            for name, hs, he in self.spans:
                if hs <= mid <= he:
                    host = name
                    break
            out.append((host, (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        ops = sorted(self.seconds_by_kind().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:top]]}


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def is_tpu_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def reduce(pd, device_plane: Callable[[str], bool] = is_tpu_plane,
           op_line: Callable[[str], bool] = lambda n: n == "XLA Ops",
           module_line: Callable[[str], bool] = lambda n: n == "XLA Modules",
           ) -> Optional[TraceSummary]:
    """``pd`` is a ``jax.profiler.ProfileData``.  The predicates pick the
    device planes and their operation and program lines (on a CPU trace,
    the host's XLA executor threads stand in for a device)."""
    ops: List[List[Op]] = []
    modules: List[List[Op]] = []
    spans: List[Tuple[str, float, float]] = []
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if device_plane(plane.name):
            chip_ops: List[Op] = []
            chip_mods: List[Op] = []
            for line in plane.lines:
                target = (chip_ops if op_line(line.name) else
                          chip_mods if module_line(line.name) else None)
                if target is None:
                    continue
                for e in line.events:
                    target.append(Op(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
            ops.append(chip_ops)
            modules.append(chip_mods)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if window is None or not ops:
        return None
    return TraceSummary(window[0], window[1], ops, modules, spans)


def reduce_dir(log_dir: str, **kw) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return None
    return reduce(ProfileData.from_file(sorted(paths)[-1]), **kw)


def remove(log_dir: str) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)
