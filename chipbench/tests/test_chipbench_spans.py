"""The program's own spans (``lb.*``), recorded in a profiler trace of a
demo-size engine box on the CPU (the host's XLA executor threads stand in
for the device, as in ``test_chipbench_trace.py``): their names, nesting
and stats, their clock against the benchmark's spans, the idle split and
the three quantities read from them, and a traced box serving exactly as an
untraced one."""

import dataclasses

import numpy as np
import pytest

import chipbench_paths  # noqa: F401  (puts the harness on sys.path)

from lib import profile, serving, spans

#: get_many calls: full misses, a latent hit, an image hit whose pixels
#: are in hand, and one id three times (full miss, latent hit, then an
#: image hit whose pixels are still being decoded in the same call).
CALLS = [[0, 1], [0, 2], [0, 3, 1], [6, 6, 6]]
NAMES = {"lb.serve_window", "lb.lookup", "lb.fetch", "lb.flush",
         "lb.assemble", "lb.decompress", "lb.warm_up", "lb.place",
         "lb.dispatch", "lb.collect", "lb.writeback"}
READERS = (spans.codec_ms, spans.hit_held_ms, spans.prep_idle_ms)
#: How the CPU trace stands in for a device (``test_chipbench_trace.py``).
CPU_PLANES = dict(device_plane=lambda n: n == "/host:CPU",
                  op_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
                  module_line=lambda n: False)
HWC = (8, 8, 4)


def _serve(trace: bool):
    """A fresh box with 7 objects serving CALLS, inside the benchmark's
    ``window`` and ``get_many`` spans when ``trace``."""
    from repro.store import LatentBox, StoreConfig
    box = LatentBox.engine(config=StoreConfig(promote_threshold=1))
    rng = np.random.default_rng(0)
    for oid in range(7):
        box.put(oid, latent=rng.standard_normal(HWC).astype(np.float16))
    out = []
    with serving.span("window", trace):
        for ids in CALLS:
            with serving.span("get_many", trace):
                out.append(box.get_many(ids))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    profile.start(d)
    try:
        results = _serve(True)
    finally:
        profile.stop()
    summary = spans.reduce_dir(d, **CPU_PLANES)
    assert summary is not None
    return results, summary, d


def _named(summary, name):
    return sorted((s for s in summary.program_spans if s.name == name),
                  key=lambda s: s.start_ns)


def _inside(child, parent) -> bool:
    return parent.start_ns <= child.start_ns and child.end_ns <= parent.end_ns


def test_every_span_is_recorded(traced):
    _, summary, _ = traced
    assert {s.name for s in summary.program_spans} == NAMES
    assert len(_named(summary, "lb.serve_window")) == len(CALLS)
    assert len(_named(summary, "lb.lookup")) == sum(map(len, CALLS))


@pytest.mark.parametrize("child,parent,shared", [
    ("lb.lookup", "lb.serve_window", "call"),
    ("lb.flush", "lb.serve_window", "call"),
    ("lb.writeback", "lb.serve_window", "call"),
    ("lb.fetch", "lb.lookup", "oid"),
    ("lb.assemble", "lb.flush", None),
    ("lb.dispatch", "lb.flush", None),
    ("lb.collect", "lb.flush", None),
    ("lb.decompress", "lb.assemble", None),
    ("lb.warm_up", "lb.assemble", None),
    ("lb.place", "lb.assemble", None),
])
def test_spans_nest(traced, child, parent, shared):
    """Each span lies inside a span of its parent's name, sharing the
    parent's ``call`` or ``oid`` where it carries one."""
    _, summary, _ = traced
    parents = _named(summary, parent)
    for c in _named(summary, child):
        around = [p for p in parents if _inside(c, p)]
        assert len(around) == 1, (c, around)
        if shared:
            assert c.stats[shared] == around[0].stats[shared]


def test_call_and_request_stats(traced):
    results, summary, _ = traced
    windows = _named(summary, "lb.serve_window")
    lookups = _named(summary, "lb.lookup")
    assert [w.stats["call"] for w in windows] == [1, 2, 3, 4]
    assert [w.stats["n"] for w in windows] == [len(c) for c in CALLS]
    classes = set()
    for ids, res, w in zip(CALLS, results, windows):
        mine = [s for s in lookups if _inside(s, w)]
        assert [s.stats["oid"] for s in mine] == ids
        assert [s.stats["cls"] for s in mine] == [r.hit_class for r in res]
        # pixels in hand: an image hit of an id not decoded in this call
        assert [s.stats["ready"] for s in mine] == [
            int(r.hit_class == "image_hit" and oid not in ids[:i])
            for i, (oid, r) in enumerate(zip(ids, res))]
        classes |= {r.hit_class for r in res}
    assert {"image_hit", "latent_hit", "full_miss"} <= classes
    assert [s.stats["ready"] for s in lookups].count(1) == 1
    fetched = {s.stats["oid"]: s.stats["bytes"]
               for s in _named(summary, "lb.fetch")}
    for s in _named(summary, "lb.decompress"):
        assert s.stats["bytes"] == fetched[s.stats["oid"]] > 0
    for s in _named(summary, "lb.place"):
        parent = [a for a in _named(summary, "lb.assemble") if _inside(s, a)]
        assert s.stats["bytes"] == parent[0].stats["bucket"] * 4 * np.prod(
            HWC)
    for s in _named(summary, "lb.collect"):
        assert s.stats["bytes"] == s.stats["bucket"] * 16 * 16 * 3
    flushes = _named(summary, "lb.flush")
    assert [f.stats["decodes"] for f in flushes] == [2, 2, 2, 1]
    assert [f.stats["chunks"] for f in flushes] == [1, 1, 1, 1]


def test_program_spans_share_the_benchmark_clock(traced):
    """Every call's program spans lie inside the benchmark's ``get_many``
    span around it, and inside the window."""
    _, summary, _ = traced
    calls = [(s, e) for name, s, e in summary.spans if name == "get_many"]
    assert len(calls) == len(CALLS)
    for w, (s, e) in zip(_named(summary, "lb.serve_window"), calls):
        assert s <= w.start_ns and w.end_ns <= e
    for p in summary.program_spans:
        assert summary.t0_ns <= p.start_ns and p.end_ns <= summary.t1_ns


def test_reduction_is_the_benchmarks_plus_the_spans(traced):
    """``spans.reduce`` changes nothing the benchmark's reduction gives."""
    _, summary, d = traced
    bench = profile.reduce_dir(d, **CPU_PLANES)
    for f in dataclasses.fields(profile.TraceSummary):
        assert getattr(summary, f.name) == getattr(bench, f.name), f.name
    assert summary.breakdown() == bench.breakdown()


@pytest.mark.parametrize("read", READERS, ids=lambda f: f.__name__)
def test_readers_read_the_spans(traced, read):
    _, summary, _ = traced
    v = read(summary)
    assert isinstance(v, float) and v >= 0
    if read is not spans.prep_idle_ms:
        assert v > 0


def test_idle_split_sums_to_the_window_idle(traced):
    _, summary, _ = traced
    idle = spans.idle_by_span(summary)
    window_idle = summary.window_s - sum(
        e - s for s, e in summary.busy_intervals(0)) * 1e-9
    assert sum(idle.values()) == pytest.approx(window_idle, rel=1e-9)
    assert set(idle) <= NAMES | {"get_many", "other"}
    assert idle.get("lb.lookup", 0) > 0
    total = spans.totals(summary)
    assert set(total) == NAMES
    assert total["lb.serve_window"][0] == len(CALLS)
    for count, total_ms, self_ms in total.values():
        assert count > 0 and 0 <= self_ms <= total_ms + 1e-9


def test_traced_box_serves_as_the_untraced_one(traced):
    results, _, _ = traced
    plain = _serve(False)
    for res, ref in zip(results, plain):
        assert [r.hit_class for r in res] == [r.hit_class for r in ref]
        for r, q in zip(res, ref):
            np.testing.assert_array_equal(r.payload, q.payload)


def _summary(program, bench=()):
    ops = [[profile.Op("fusion.1", 10.0, 20.0),
            profile.Op("fusion.2", 60.0, 70.0)]]
    return spans.SpanTrace(0.0, 100.0, ops, [[]], list(bench), program)


def test_innermost_span_takes_the_time():
    """Self time and idle go to the innermost span; a benchmark span only
    where no program span is over the instant."""
    span = spans.ProgramSpan
    t = _summary([span("lb.serve_window", 5.0, 80.0, {"call": 1}),
                  span("lb.assemble", 15.0, 40.0, {}),
                  span("lb.decompress", 30.0, 35.0, {})],
                 [("get_many", 0.0, 90.0)])
    assert spans.partition(t) == [
        (0.0, 5.0, "get_many"), (5.0, 15.0, "lb.serve_window"),
        (15.0, 30.0, "lb.assemble"), (30.0, 35.0, "lb.decompress"),
        (35.0, 40.0, "lb.assemble"), (40.0, 80.0, "lb.serve_window"),
        (80.0, 90.0, "get_many"), (90.0, 100.0, "other")]
    idle = spans.idle_by_span(t)
    assert idle == pytest.approx({"get_many": 15e-9, "lb.serve_window":
                                  35e-9, "lb.assemble": 15e-9,
                                  "lb.decompress": 5e-9, "other": 10e-9})
    want = {"lb.assemble": (1, 25e-6, 20e-6),
            "lb.decompress": (1, 5e-6, 5e-6),
            "lb.serve_window": (1, 75e-6, 50e-6)}
    assert spans.totals(t) == {k: pytest.approx(v) for k, v in want.items()}
    assert spans.prep_idle_ms(t) == pytest.approx(20e-6)


def test_a_program_without_spans_reads_nothing():
    """A trace of a program that records no ``lb.*`` spans: the readers
    find nothing, and the idle falls to the benchmark's spans."""
    t = _summary([], [("get_many", 0.0, 50.0)])
    for read in READERS:
        assert read(t) is None
    assert spans.idle_by_span(t) == pytest.approx(
        {"get_many": 40e-9, "other": 40e-9})
    assert spans.totals(t) == {}
