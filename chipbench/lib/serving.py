"""The client side of a run: the serving loops that drive
``LatentBox.get_many`` on the wall clock, and the compile counter.

Open loop: one thread takes every request that is due, up to ``window``
of them, and serves them in one ``get_many`` call, or sleeps until the
next is due.  A request's latency runs from its due time to the return of
the call that served it, so a pixel hit that shares a call with decodes
pays for them.  Requests due in the window that are still queued at its
end are served after it (drained) with their whole wait counted; any
still unserved ``drain_s`` after the end count as failed, with the time
they waited until then as their (lower-bound) latency.

Closed loop: the next call is issued when the last one returns; the rate
is every request completed over the time from the window's start to the
return of the first call that ends past ``seconds``.

Host spans (``jax.profiler.TraceAnnotation``) mark ``window``,
``get_many`` and ``await_arrival`` so that the trace reduction can say
what the host was doing in each idle gap of the device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List

import numpy as np

BATCHER_KEYS = ("decodes", "batches", "padded_slots", "coalesced",
                "decompressions", "memo_hits")


class CompileCounter:
    """Counts executables built (fresh compiles and persistent-cache loads
    alike) through JAX's backend-compile monitoring event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_event)


def span(name: str, enabled: bool):
    if not enabled:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


@dataclasses.dataclass
class Call:
    first: int                  # index of its first request
    n: int
    batcher: Dict[str, int]     # batcher counter deltas over the call


@dataclasses.dataclass
class Window:
    """What one measured window served."""
    end_s: float                # when its last call returned
    ids: np.ndarray             # requests attempted, in order
    latency_s: np.ndarray       # nan where unserved
    #: every attempted request, an unserved one at the time it waited
    #: until the loop gave up (a lower bound, past every served latency)
    latency_all_s: np.ndarray
    results: List[Any]          # GetResult or None
    calls: List[Call]
    wake_late_s: List[float]    # how late the open-loop sleeper woke

    @property
    def served(self) -> np.ndarray:
        return ~np.isnan(self.latency_s)


def _batcher_stats(box) -> Dict[str, int]:
    stats = box.backend.engine.batcher.stats
    return {k: int(stats.get(k, 0)) for k in BATCHER_KEYS}


def _serve(box, ids, first, n, t0, calls, results, trace):
    before = _batcher_stats(box)
    with span("get_many", trace):
        res = box.get_many([int(i) for i in ids[first:first + n]])
    end = time.perf_counter() - t0
    after = _batcher_stats(box)
    calls.append(Call(first, n,
                      {k: after[k] - before[k] for k in BATCHER_KEYS}))
    results[first:first + n] = res
    return end


def serve_open(box, ids: np.ndarray, due_s: np.ndarray, window: int,
               seconds: float, drain_s: float, trace: bool = False,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    n = len(ids)
    latency = np.full(n, np.nan)
    results: List[Any] = [None] * n
    calls: List[Call] = []
    wake_late: List[float] = []
    i = 0
    t0 = clock()
    end = 0.0
    while i < n:
        now = clock() - t0
        if now >= seconds + drain_s:
            break
        if due_s[i] > now:
            with span("await_arrival", trace):
                time.sleep(due_s[i] - now)
            wake_late.append(clock() - t0 - due_s[i])
            continue
        j = i
        while j < n and j - i < window and due_s[j] <= now:
            j += 1
        end = _serve(box, ids, i, j - i, t0, calls, results, trace)
        latency[i:j] = end - due_s[i:j]
        i = j
    stop = clock() - t0
    unserved = np.isnan(latency)
    return Window(max(end, seconds), ids, latency,
                  np.where(unserved, stop - due_s, latency), results, calls,
                  wake_late)


def serve_closed(box, ids: np.ndarray, per_call: int, seconds: float,
                 trace: bool = False,
                 clock: Callable[[], float] = time.perf_counter) -> Window:
    n = len(ids)
    latency = np.full(n, np.nan)
    results: List[Any] = [None] * n
    calls: List[Call] = []
    i, end = 0, 0.0
    t0 = clock()
    while end < seconds:
        if i + per_call > n:
            raise RuntimeError("the closed loop ran out of generated ids")
        start = clock() - t0
        end = _serve(box, ids, i, per_call, t0, calls, results, trace)
        latency[i:i + per_call] = end - start
        i += per_call
    return Window(end, ids[:i], latency[:i], latency[:i], results[:i],
                  calls, [])


def serve_prefix(box, ids: np.ndarray, window: int) -> None:
    """Closed-loop calls of ``window`` ids, before the window (set-up)."""
    for s in range(0, len(ids), window):
        box.get_many([int(i) for i in ids[s:s + window]])


def percentile_ms(latency_s: np.ndarray, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), in ms."""
    return float(np.percentile(latency_s, q) * 1e3)
