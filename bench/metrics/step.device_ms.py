"""Fused step: device time of the serving step's programs in the trace,
per engine step of the window, in ms."""
from bench import trace

NEEDLES = ("step_paged",)


def read(run):
    if run.trace is None or not run.window.steps:
        return None
    t, n = trace.time_in(run.trace["modules"], NEEDLES, *run.span)
    return t / 1e6 / run.window.steps if n else None
