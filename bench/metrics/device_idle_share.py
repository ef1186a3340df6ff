"""Device: the share of the traced window in which no operation ran on
the device (1 - union of op intervals / window), in %."""
from bench import trace


def read(run):
    if run.trace is None or not run.trace["ops"]:
        return None
    lo, hi = run.span
    return 100.0 * (1.0 - trace.busy_ns(run.trace["ops"], lo, hi)
                    / (hi - lo))
