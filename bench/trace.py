"""From a profiler trace to the numbers the per-layer readers need.

`load` reads the ``.xplane.pb`` the JAX profiler wrote into plain event
lists: device operations and programs (lines ``XLA Ops`` and ``XLA
Modules`` of each ``/device:`` plane) and the benchmark's own host spans
(``bench.*`` `TraceAnnotation` events).  Everything else here is
arithmetic on those lists, so the tests check it on a recorded sample.
An event is ``(name, start_ns, duration_ns)``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def short(name: str) -> str:
    """An op's instruction name without its HLO text: ``%fusion.12 =
    bf16[...] fusion(...)`` is ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Dict[str, object]:
    """The first device's op and program events and the host's
    ``bench.*`` spans, read in one pass over the trace's planes, with the
    event count of every line seen."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = dict(ops=[], modules=[], spans=[], lines={})
    device = None
    for p in pd.planes:
        if p.name.startswith("/device:") and device is None:
            device = p.name
        for ln in p.lines:
            evs = [(short(e.name), e.start_ns, e.duration_ns)
                   for e in ln.events]
            out["lines"][f"{p.name}|{ln.name}"] = len(evs)
            if p.name == device and ln.name == OPS_LINE:
                out["ops"] = evs
            elif p.name == device and ln.name == MODULES_LINE:
                out["modules"] = evs
            elif p.name.startswith("/host:"):
                out["spans"].extend(e for e in evs
                                    if e[0].startswith(SPAN_PREFIX))
    return out


def extent(spans: Sequence[Event]) -> Tuple[float, float]:
    """[first start, last end] of the host spans: the traced window."""
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))


def union(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged busy intervals of ``events`` clipped to ``[lo, hi]``."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s + d > lo and s < hi)
    out: List[Tuple[float, float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(events, lo, hi))


def time_in(events: Sequence[Event], needles: Sequence[str],
            lo: float, hi: float) -> Tuple[float, int]:
    """Summed duration and count of events whose name holds any needle,
    counting those that start inside ``[lo, hi)``."""
    t, n = 0.0, 0
    for name, s, d in events:
        if lo <= s < hi and any(k in name for k in needles):
            t += d
            n += 1
    return t, n


def top_ops(events: Sequence[Event], lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` op names with the most device seconds in the window."""
    tot: Dict[str, float] = {}
    for name, s, d in events:
        if lo <= s < hi:
            tot[name] = tot.get(name, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(n, t / 1e9) for n, t in best]


def idle_gaps(events: Sequence[Event], spans: Sequence[Event], lo: float,
              hi: float, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest device-idle gaps in the window, each named by the
    host span that overlaps it most (``idle`` where none does)."""
    busy = union(events, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        best, cover = "idle", 0.0
        for name, s, d in spans:
            ov = min(b, s + d) - max(a, s)
            if ov > cover:
                best, cover = name, ov
        out.append((best, (b - a) / 1e9))
    return out
