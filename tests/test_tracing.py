"""Spans, counters and stamps inside the serving path.

The engine and backend phases are `TraceAnnotation` spans named in
`repro.serving.tracing.SPANS`; a traced run must emit every one of them,
nest the device wait (`backend.fetch`) and the dispatch inside their
`engine.step`, and serve exactly the tokens an untraced run serves.  The
backend counts live against padded query rows, and requests carry the
host time they were first queued and first admitted.
"""
import glob
import os

import jax
import pytest

from repro.configs import get_config
from repro.core.advisory import InferenceRequest
from repro.core.node_manager import NodeManager
from repro.models.registry import get_model
from repro.serving.backend import LaneWork, RealBackend, _bucket
from repro.serving.cost_model import CostModel, HardwareSpec
from repro.serving.engine import NodeEngine
from repro.serving.tracing import SPANS

PAGE = 8
_MODEL = {}


def _model():
    if not _MODEL:
        cfg = get_config("llama3-8b").reduced(dtype="float32", n_kv_heads=2)
        model = get_model(cfg)
        _MODEL["m"] = (cfg, model, model.init(jax.random.key(0)))
    return _MODEL["m"]


def _engine(token_budget=16, max_batch=4):
    cfg, model, params = _model()
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    be = RealBackend(cfg, model, params, mgr=mgr, n_pages=64,
                     page_size=PAGE)
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=max_batch, backend=be,
                     token_budget=token_budget)
    return cfg, be, eng


def _request(cfg, sid, n_prompt, n_new, seed):
    ids = [(seed * 31 + 7 * i) % cfg.vocab for i in range(n_prompt)]
    return InferenceRequest(session_id=sid, prompt_tokens=n_prompt,
                            max_new_tokens=n_new, prompt_ids=ids)


def _serve(trace_dir=None):
    """Two requests, one preempted mid-decode (a swap-out, then a swap-in
    on readmission), served to the end; optionally under the profiler.
    Returns (tokens by session, the requests, the preempted one)."""
    cfg, be, eng = _engine()
    reqs = [_request(cfg, "a", 12, 6, 1), _request(cfg, "b", 20, 5, 2)]
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        for r in reqs:
            eng.submit(r)
        now, steps, victim = 0.0, 0, None
        while eng.waiting or eng.running:
            if steps == 3:
                victim = eng.preempt_one(now)
            now += max(eng.step(now), 1e-9)
            steps += 1
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return {r.session_id: list(r.output_ids) for r in reqs}, reqs, victim


def _spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in ln.events
                       if e.name.startswith(("engine.", "backend.",
                                             "transfer.")))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    toks, reqs, victim = _serve(d)
    return dict(spans=_spans(d), toks=toks, reqs=reqs, victim=victim)


def test_every_span_is_emitted_and_named(traced):
    assert traced["victim"] is not None
    assert {n for n, *_ in traced["spans"]} == set(SPANS)


@pytest.mark.parametrize("inner", ["backend.fetch", "backend.dispatch",
                                   "backend.assemble", "engine.admit"])
def test_phase_spans_nest_inside_their_engine_step(traced, inner):
    steps = [(s, e) for n, s, e, _ in traced["spans"] if n == "engine.step"]
    got = [(s, e) for n, s, e, _ in traced["spans"] if n == inner]
    assert got
    for s, e in got:
        assert any(a <= s and e <= b for a, b in steps), (inner, s, e)


def test_dispatch_span_carries_its_bucket(traced):
    args = [a for n, _, _, a in traced["spans"] if n == "backend.dispatch"]
    assert args
    for a in args:
        assert a["Bb"] == _bucket(a["lanes"])
        assert a["lanes"] <= a["rows"] <= a["Bb"] * a["Sqb"]
        assert a["Sqb"] >= 1 and a["Tb"] >= 1
    # the first step admits both requests into the 16-token budget: 12
    # prompt tokens of one and the first 4 of the other, one dispatch
    assert {k: args[0][k] for k in ("Bb", "Sqb", "lanes", "rows")} == \
        dict(Bb=2, Sqb=16, lanes=2, rows=16)
    steps = [a for n, _, _, a in traced["spans"] if n == "engine.step"]
    assert (steps[0]["lanes"], steps[0]["prompt_tokens"]) == (2, 16)


def test_profiler_does_not_change_tokens(traced):
    toks, _, _ = _serve()
    assert toks == traced["toks"]


def test_admit_spans_name_the_requests_admitted(traced):
    ids = [i for n, _, _, a in traced["spans"] if n == "engine.admit"
           for i in str(a.get("ids", "")).split()]
    victim = traced["victim"]
    for r in traced["reqs"]:
        assert r.queued_at <= r.admitted_at
        # admitted once, and the preempted request once more on its return
        assert ids.count(str(r.request_id)) == 1 + (r is victim)


def _lane(req, new=0):
    """A lane that emits a token: a whole prompt, or a decode step."""
    return LaneWork(req=req, new_tokens=new, is_decode=not new)


@pytest.mark.parametrize("split", [False, True], ids=["fused", "split"])
def test_row_counters_on_a_known_plan(split):
    """Σ q_len over live lanes and Σ Bb x Sqb over every dispatch,
    counted by hand for a plan of prefill chunks, a mixed step and decode
    steps; with the skew split on, one step issues two dispatches."""
    cfg, be, _ = _engine()
    if not split:
        be.split_skew = 1.0
    a = _request(cfg, "a", 40, 4, 1)
    b = _request(cfg, "b", 3, 4, 2)
    c = _request(cfg, "c", 2, 4, 3)
    live = padded = 0
    # step 1: a's 40-token prompt alone: one lane of 40 -> (1, 64)
    be.step([_lane(a, 40)], 0.0)
    live, padded = live + 40, padded + 1 * 64
    # step 2: a decodes (1 row) beside b's 3-token and c's 2-token prompts
    be.step([_lane(a), _lane(b, 3), _lane(c, 2)], 0.0)
    if split:
        # a's table (6 pages -> 8) is 8x the median's (1): two dispatches,
        # {b, c} with Sqb 4 and Bb 2, and a alone with Sqb 1
        live, padded = live + 6, padded + 2 * 4 + 1 * 1
    else:
        live, padded = live + 6, padded + 4 * 4
    # step 3: all three decode, one row each
    be.step([_lane(r) for r in (a, b, c)], 0.0)
    live, padded = live + 3, padded + (2 * 1 + 1 * 1 if split else 4 * 1)
    assert be.stats["split_steps"] == (2 if split else 0)
    assert be.stats["live_rows"] == live
    assert be.stats["padded_rows"] == padded


def test_queued_and_admitted_stamps_survive_preemption():
    cfg, be, eng = _engine()
    r = _request(cfg, "s", 10, 8, 4)
    eng.submit(r)
    q0 = r.queued_at
    eng.step(0.0)
    a0 = r.admitted_at
    assert q0 is not None and q0 <= a0
    eng.step(0.0)
    assert eng.preempt_one(0.0) is r
    while eng.waiting or eng.running:
        eng.step(0.0)
    assert (r.queued_at, r.admitted_at) == (q0, a0)
    assert len(r.output_ids) == 8
