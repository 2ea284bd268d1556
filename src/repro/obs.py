"""Spans on the profiler's clock.

``span(name, **args)`` marks one layer boundary of the program — a flow
pass, a decode, a device call, a host finish, a replay, an oracle run —
as a :class:`jax.profiler.TraceAnnotation`, so a ``jax.profiler`` trace
shows what the host was doing beside the device's own events, on one
clock.  ``args`` are the span's counters (rows, machines, bytes, …);
more can be added once they are known with ``set_metadata(**args)`` on
the object the ``with`` statement binds.

An active profiler session is the only switch: wrap any call in
``jax.profiler.trace(dir)`` and its spans are recorded; outside one a
span costs about a microsecond.  A process that never imported JAX can
have no profiler running, so there ``span`` returns a shared no-op and
tracing never imports JAX on the host-only paths.
"""

from __future__ import annotations

import sys
from typing import Any

__all__ = ["span", "recording"]


class _Off:
    """The no-op span: a reusable context that records nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def set_metadata(self, **args: Any) -> None:
        return None


_OFF = _Off()


def span(name: str, **args: Any) -> Any:
    """A context manager that records ``name`` with ``args`` while a
    ``jax.profiler`` session is active (a no-op when JAX is not
    loaded)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)


def recording() -> bool:
    """Is a ``jax.profiler`` session recording spans?  (Work done only
    for a trace's reader checks this first.)"""
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()
