"""The program's own host spans, read again from the trace `bench/run.py`
wrote.

`bench/trace.py` keeps the harness's ``bench.*`` spans and the device ops.
The program names more: host spans around the phases of a step
(``engine.*``, ``backend.*``, ``transfer.*``; `repro.serving.tracing`).
`load` finds the ``.xplane.pb`` under ``results/trace/`` whose device op
count is the one `trace.load` read for this run, parses it once for every
reader, and keeps the result on ``run``.  A program without these spans
(an older commit) gives an empty list, and its readers report nothing.

The arithmetic (`step_host_ns`) works on plain span lists, so the tests
check it on synthetic spans.  A span is ``(name, start_ns, duration_ns,
args)``.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

ROOT = Path(__file__).resolve().parents[1]
SPAN_PREFIXES = ("engine.", "backend.", "transfer.")

Span = Tuple[str, float, float, dict]


def _candidates() -> List[str]:
    files = glob.glob(os.path.join(str(ROOT), "results", "trace", "*", "**",
                                   "*.xplane.pb"), recursive=True)
    return sorted(files, key=os.path.getmtime, reverse=True)


def parse(path: str) -> Dict[str, object]:
    """The program's host spans and the number of the first device plane's
    ops (0 without a device plane, as on the CPU), in one pass over the
    file's planes."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    n_ops, device = 0, None
    for p in ProfileData.from_file(path).planes:
        if p.name.startswith("/device:") and device is None:
            device = p.name
        for ln in p.lines:
            if p.name == device and ln.name == trace.OPS_LINE:
                n_ops = sum(1 for _ in ln.events)
            elif p.name.startswith("/host:"):
                spans.extend((e.name, e.start_ns, e.duration_ns,
                              dict(e.stats)) for e in ln.events
                             if e.name.startswith(SPAN_PREFIXES))
    return dict(spans=spans, n_ops=n_ops)


def load(run) -> Optional[Dict[str, object]]:
    """`parse` of the trace this run wrote: the newest file under
    ``results/trace/`` with as many device ops as ``run.trace`` holds.
    None when no traced run left one."""
    if run.trace is None:
        return None
    if "program_trace" in vars(run):
        return run.program_trace
    want = len(run.trace["ops"])
    run.program_trace = next(
        (got for got in map(parse, _candidates()) if got["n_ops"] == want),
        None)
    return run.program_trace


def step_host_ns(spans: Sequence[Span], lo: float, hi: float
                 ) -> List[float]:
    """Per ``engine.step`` span starting in ``[lo, hi)``: its duration less
    the ``backend.fetch`` time inside it, where the host waits for the
    device."""
    fetch = [(n, s, d) for n, s, d, _ in spans if n == "backend.fetch"]
    return [d - trace.busy_ns(fetch, s, s + d) for n, s, d, _ in spans
            if n == "engine.step" and lo <= s < hi]
