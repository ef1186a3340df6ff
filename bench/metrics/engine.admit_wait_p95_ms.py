"""Engine: 95th percentile of the wait from the engine queueing a request
to first admitting it, by the program's own stamps (``queued_at``,
``admitted_at``, host ``time.perf_counter``), in ms.  Requests the engine
received in the window count; one never admitted waits until the close.
Turns the harness still held behind their session's reply never reached
the engine and are left out."""
from bench import tails


def read(run):
    got = [r for r in run.window.records if r.req is not None
           and getattr(r.req, "queued_at", None) is not None]
    if not got:
        return None
    # the close on the program's clock: no submit precedes its turn's
    # scheduled arrival, so the window opened by the earliest
    # queued_at - at, and closes its seconds later
    opened = min(r.req.queued_at - r.at for r in got)
    close = opened + run.window.seconds
    waits = [(r.req.admitted_at if r.req.admitted_at is not None
              else close) - r.req.queued_at for r in got]
    return 1e3 * tails.p95(waits)
