"""Engine: 95th percentile of the wait from a request's scheduled arrival
to the start of the step that admitted it (host clock), in ms.  One never
admitted waits until the close."""
from bench import tails


def read(run):
    waits = tails.queue_waits(run.window.records, run.window.seconds)
    return 1e3 * tails.p95(waits) if waits else None
