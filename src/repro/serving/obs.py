"""The serving path's host clock and its spans.

``now_ns()`` is the one clock the program stamps requests with
(``ServeRequest.submit_ns`` and the rest): epoch nanoseconds, the clock
the JAX profiler stamps host events with. A profiler trace stores its
times as offsets from its ``profile_start_time`` (a stat of the trace's
"Task Environment" plane) on this same clock, so a stamp taken inside a
span falls inside that span once the offset is added.

``span(name, **meta)`` marks a piece of serving work (every name starts
with ``serve.``). Inside a profiler session it is a
``jax.profiler.TraceAnnotation``: the span lands in the trace beside the
device ops it dispatched, with ``meta`` as its stats. Outside a session
it is one shared object that records nothing, so a span costs a fraction
of a microsecond. Nothing else turns spans on or off.

    with span("serve.decode", rows=len(live)):
        ...
    with span("serve.admit") as sp:
        ...
        sp.set_metadata(n=len(admitted))
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


def now_ns() -> int:
    """Host time in epoch nanoseconds, on the profiler's clock."""
    return time.time_ns()


class _Off:
    """A span outside a profiler session: records nothing."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **meta) -> None:
        pass


_OFF = _Off()


def recording() -> bool:
    """Whether a profiler session runs, so that spans record."""
    return TraceAnnotation.is_enabled()


def span(name: str, **meta):
    """A context manager over ``TraceAnnotation(name, **meta)`` while a
    profiler session runs, else the shared no-op span. Either one takes
    ``set_metadata(**meta)`` for what is known only at the span's end."""
    if not recording():
        return _OFF
    return TraceAnnotation(name, **meta)
