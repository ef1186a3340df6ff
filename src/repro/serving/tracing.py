"""Named host spans of the serving path, on the profiler's clock.

`span` is a `jax.profiler.TraceAnnotation`: a TraceMe event the profiler
keeps with the device trace, so a span shares the device ops' timebase by
construction.  With no profiler running it records nothing and costs about
a microsecond.  Its args are host ints the caller already holds: a span
never reads a device value and never waits for the device.

`SPANS` names every span the program emits, so a reader matches names
exactly.  The device side of the step is named by `jax.named_scope`s in
`DenseLM._step_paged_impl` (`layers`, `attn`, `kv_write`, `ffn`, `head`).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SPANS = (
    "engine.step",        # all of NodeEngine.step: lanes, prompt_tokens
    "engine.poll",        # reaping finished tier transfers at its head
    "engine.admit",       # admission: ids of the requests admitted
    "engine.advance",     # advancing every lane by what the step did
    "backend.resident",   # swap-ins and inbound fences (StepResult.stall)
    "backend.grow",       # lane ids, page shortfall, CoW forks, extension
    "backend.assemble",   # one dispatch's ids, tables and KV slots
    "backend.dispatch",   # enqueueing step_paged: Bb, Sqb, Tb, lanes, rows
    "backend.fetch",      # the host waiting for the step's token ids
    "transfer.launch",    # one tier copy launched: kind, bytes
    "transfer.fence",     # waiting on in-flight tier copies: kind
)


def span(name: str, **args) -> TraceAnnotation:
    """A context manager timing ``name`` while a profiler runs; more args
    can be added before it closes with ``set_metadata``."""
    return TraceAnnotation(name, **args)


def recording() -> bool:
    """Whether a profiler is recording spans: args that cost work to
    build are built only then."""
    return TraceAnnotation.is_enabled()
