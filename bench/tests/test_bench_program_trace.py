"""The readers of the program's own spans, counters and stamps: their
arithmetic on synthetic spans, what they report for a program that lacks
these names, and a tiny traced run on the CPU."""
import json
from types import SimpleNamespace

import jax
import pytest

from bench import program_trace, run, serve

SPANS = [
    ("engine.step", 0, 100, dict(lanes=3, prompt_tokens=0)),
    ("backend.dispatch", 20, 5, dict(Bb=4, Sqb=1, Tb=8, lanes=3, rows=3)),
    ("backend.fetch", 40, 30, {}),
    ("backend.fetch", 80, 10, {}),
    ("engine.step", 200, 50, dict(lanes=1, prompt_tokens=0)),
    ("backend.fetch", 300, 20, {}),          # outside every step
]


def _run(spans=SPANS, steps=2, stats=None, records=()):
    win = serve.Window(seconds=1.0, steps=steps, records=list(records),
                       stats=dict(stats or {}))
    r = SimpleNamespace(window=win, span=(0, 400),
                        trace=dict(ops=[], modules=[], spans=[]))
    r.program_trace = dict(spans=spans, n_ops=0)
    return r


def test_host_ms_subtracts_the_fetches_inside_each_step():
    assert program_trace.step_host_ns(SPANS, 0, 400) == [60.0, 50.0]
    assert program_trace.step_host_ns(SPANS, 150, 400) == [50.0]
    got = run.load_reader("step.host_ms")(_run())
    assert got == pytest.approx((60 + 50) / 2 / 1e6)


def test_row_fill_reads_the_window_counters():
    read = run.load_reader("step.row_fill")
    assert read(_run(stats=dict(live_rows=30, padded_rows=120))) == 25.0
    assert read(_run(stats=dict(live_rows=0, padded_rows=0))) is None


def _record(at, queued, admitted):
    req = SimpleNamespace(queued_at=queued, admitted_at=admitted)
    return serve.Record(req=req, sid="s", at=at, prefix=[])


def test_admit_wait_takes_the_program_stamps():
    read = run.load_reader("engine.admit_wait_p95_ms")
    # the window opened at program time 100: the earliest submit less its
    # scheduled arrival.  Waits 10 ms, 30 ms, and one never admitted,
    # queued at 100.7, that waits to the close at 101.0; a turn the
    # harness still held (req None) never reached the engine
    recs = [_record(0.2, 100.2, 100.21), _record(0.5, 100.5005, 100.5305),
            _record(0.7, 100.7, None),
            serve.Record(req=None, sid="t", at=0.9, prefix=[])]
    got = read(_run(records=recs))
    waits = sorted([10.0, 30.0, 300.0])
    assert got == pytest.approx(waits[1] + 0.9 * (waits[2] - waits[1]))


def test_readers_report_nothing_for_a_program_without_these_names():
    """An older program: no spans, no row counters, requests without
    stamps."""
    r = _run(spans=[], stats=dict(dma_pages=3, grid_pages=8),
             records=[serve.Record(req=SimpleNamespace(), sid="s", at=0.1,
                                   prefix=[])])
    for name in ("step.host_ms", "step.row_fill",
                 "engine.admit_wait_p95_ms"):
        assert run.load_reader(name)(r) is None, name


def test_load_picks_the_trace_with_this_runs_op_count(monkeypatch):
    files = {"new.xplane.pb": dict(n_ops=3, spans=[]),
             "old.xplane.pb": dict(n_ops=5, spans=[1])}
    monkeypatch.setattr(program_trace, "_candidates", lambda: list(files))
    monkeypatch.setattr(program_trace, "parse", files.__getitem__)
    r = SimpleNamespace(trace=dict(ops=[None] * 5))
    assert program_trace.load(r) is files["old.xplane.pb"]
    assert program_trace.load(SimpleNamespace(trace=dict(ops=[]))) is None
    assert program_trace.load(SimpleNamespace(trace=None)) is None


def test_traced_cpu_run_reports_the_host_readers(tiny, tiny_mix, tmp_path,
                                                 monkeypatch):
    # a root of its own, so that no other test's traced run shares the
    # trace directory
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(program_trace, "ROOT", tmp_path)
    spec = json.loads((run.BENCH.parent / "BENCHMARK.json").read_text())
    out = run.measure(spec, spec["workloads"][0], tiny, tiny_mix, 2**31 + 5,
                      3.0, True, dict(bf16_flops=1e12, hbm_bytes_per_s=1e11),
                      jax.devices(), kernel_mode="ref")
    assert out["correct"] is True, out["compared"]
    got = out["metrics"]
    assert {"engine.admit_wait_p95_ms", "step.host_ms",
            "step.row_fill"} <= set(got)
    assert 0 < got["step.row_fill"]["value"] <= 100
    assert got["step.host_ms"]["value"] > 0
    assert got["engine.admit_wait_p95_ms"]["value"] >= 0
