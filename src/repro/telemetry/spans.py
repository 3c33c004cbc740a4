"""Named host spans in the profiler's trace (DESIGN_TELEMETRY.md §7).

``span(name, **counters)`` is a ``jax.profiler.TraceAnnotation``: it
records nothing unless a profiler trace is running, and then writes the
span, its counters as the event's arguments, into that trace, on the
same host clock as the runtime's own events. The profiler is the buffer
and starting it is the switch; there is no other.

Counters must be plain host values. A ``jax.Array`` (or a numpy scalar
standing in for one) would force a device sync, or a copy, on every
step, so anything but ``int``, ``float``, ``bool`` or ``str`` raises.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

_PLAIN = (int, float, bool, str)


def _plain(name: str, counters: dict) -> dict:
    for k, v in counters.items():
        if not isinstance(v, _PLAIN):
            raise TypeError(
                f"span {name!r}: counter {k!r} is a {type(v).__name__}; "
                "spans take int, float, bool or str values only")
    return counters


class Span(TraceAnnotation):
    """A profiler span whose counters are checked; ``count`` adds
    counters known only once the spanned work has run."""

    def __init__(self, name: str, **counters):
        super().__init__(name, **_plain(name, counters))
        self._name = name

    def count(self, **counters) -> None:
        self.set_metadata(**_plain(self._name, counters))


def span(name: str, **counters) -> Span:
    """A span named ``name`` with ``counters`` as its arguments."""
    return Span(name, **counters)
