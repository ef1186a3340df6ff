"""The trace reducer on a recorded trace sample: busy union, idle gaps
and whose host span they fall in, kernel and program time."""
import json
from pathlib import Path

from bench import trace

SAMPLE = json.loads((Path(__file__).parent
                     / "trace_sample.json").read_text())


def test_union_merges_overlaps_and_clips():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 50, 100)]
    assert trace.union(ev, 2, 60) == [(2, 15), (30, 35), (50, 60)]
    assert trace.busy_ns(ev, 2, 60) == 13 + 5 + 10


def test_idle_gaps_are_named_by_the_host_span_over_them():
    ops = [("op", 0, 10), ("op", 40, 10)]
    spans = [("bench.step", 0, 12), ("bench.wait", 12, 28),
             ("bench.step", 40, 20)]
    gaps = trace.idle_gaps(ops, spans, 0, 60)
    assert gaps == [("bench.wait", 30e-9), ("bench.step", 10e-9)]


def test_time_in_and_top_ops():
    ops = [("fusion.1", 0, 5), ("custom-call.paged", 5, 20),
           ("fusion.1", 30, 5), ("copy", 100, 1)]
    assert trace.time_in(ops, ("paged",), 0, 50) == (20, 1)
    assert trace.top_ops(ops, 0, 50) == [("custom-call.paged", 20e-9),
                                         ("fusion.1", 10e-9)]


def test_recorded_sample():
    ops = [tuple(e) for e in SAMPLE["ops"]]
    spans = [tuple(e) for e in SAMPLE["spans"]]
    lo, hi = trace.extent(spans)
    busy = trace.busy_ns(ops, lo, hi)
    assert 0 < busy <= hi - lo
    # the union never counts an instant twice: it is at most the sum
    assert busy <= sum(d for _, s, d in ops if lo <= s < hi) + 1
    gaps = trace.idle_gaps(ops, spans, lo, hi)
    assert gaps and all(name.startswith("bench.") or name == "idle"
                        for name, _ in gaps)
    assert sum(g for _, g in gaps) <= (hi - lo - busy) / 1e9 + 1e-9
    assert [g for _, g in gaps] == sorted((g for _, g in gaps),
                                          reverse=True)
    t, n = trace.time_in(ops, SAMPLE["kernel_needles"], lo, hi)
    assert n > 0 and 0 < t <= busy
    mods = [tuple(e) for e in SAMPLE["modules"]]
    t, n = trace.time_in(mods, SAMPLE["step_needles"], lo, hi)
    assert n > 0 and t > 0
    assert trace.top_ops(ops, lo, hi)[0][1] > 0
