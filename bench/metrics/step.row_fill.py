"""Fused step: live query rows over the rows the step programs ran, by
the backend's counters (``live_rows``: each lane's tokens; ``padded_rows``:
lanes bucket times tokens bucket), over the window, in %."""


def read(run):
    live = run.window.stats.get("live_rows")
    padded = run.window.stats.get("padded_rows")
    return 100.0 * live / padded if live is not None and padded else None
