"""Engine: host time of an engine step in which the device waits on the
host, by the program's spans: the mean over the window's ``engine.step``
spans of each span's duration less the ``backend.fetch`` time inside it,
in ms."""
from bench import program_trace


def read(run):
    pt = program_trace.load(run)
    if pt is None:
        return None
    host = program_trace.step_host_ns(pt["spans"], *run.span)
    return sum(host) / len(host) / 1e6 if host else None
