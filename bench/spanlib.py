"""Readers over the program's own spans, for the per-layer metrics in
``metrics/``.

The program opens ``repro.*`` spans (``jax.profiler.TraceAnnotation``)
at each host layer's boundary on the thread that runs the request, so
they land on the host line :func:`tracereduce.extract` keeps, beside
``bench.window`` and on the device's clock.  Each reader takes a traced
:class:`harness.Run`, clips every span to the window, and returns
``None`` when the window holds none of the spans it reads (a program
without them, or a cell that does not run that layer).

A layer's self time is the time in which its span is the innermost
``repro.*`` span open: nested ``repro.*`` spans are their own layers,
JAX's own events inside a span (``np.asarray(jax.Array)``,
``PjitFunction(...)``) stay in its self time.  Shares are in percent of
the window.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import tracereduce

__all__ = ["PREFIX", "self_times", "self_share", "idle_unattributed",
           "per_request"]

PREFIX = "repro."


def _spans(run: Any) -> List[Tuple[str, float, float]]:
    """The window's ``repro.*`` spans as ``(name, start, end)`` in ns,
    clipped to the window, outermost first where two start together."""
    red = run.reduction
    out = []
    for name, s, d in red.host:
        if not name.startswith(PREFIX):
            continue
        s, e = max(float(s), red.t0), min(float(s) + float(d), red.t1)
        if e > s:
            out.append((name, s, e))
    out.sort(key=lambda x: (x[1], x[1] - x[2]))
    return out


def self_times(run: Any) -> Dict[str, float]:
    """Seconds in which each span name is the innermost ``repro.*``
    span open, over the window."""
    tot: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []     # (name, end), innermost last
    t = 0.0

    def credit(name: str, until: float) -> None:
        nonlocal t
        if until > t:
            tot[name] = tot.get(name, 0.0) + until - t
            t = until

    def advance(limit: float) -> None:
        # close the spans that end by ``limit``, crediting each stretch
        # to the span innermost in it; a stretch with none open is no
        # one's
        nonlocal t
        while stack and stack[-1][1] <= limit:
            credit(*stack.pop())
        if stack:
            credit(stack[-1][0], limit)
        t = max(t, limit)

    for name, s, e in _spans(run):
        advance(s)
        stack.append((name, e))
    advance(float("inf"))
    return {k: v / 1e9 for k, v in tot.items()}


def self_share(run: Any, name: str) -> Optional[float]:
    """Self time of the span ``name`` (or, for a name ending in ``.``,
    of every span under that prefix) in percent of the window."""
    times = self_times(run)
    hits = [v for k, v in times.items()
            if (k.startswith(name) if name.endswith(".") else k == name)]
    if not hits:
        return None
    return 100.0 * sum(hits) / run.reduction.window_s


def idle_unattributed(run: Any) -> Optional[float]:
    """Share of the window in which no program runs on a device and no
    ``repro.*`` span is open: the idle time the spans leave unexplained,
    in percent."""
    red = run.reduction
    spans = _spans(run)
    if not spans:
        return None
    covered = [(s, e) for _, s, e in spans]
    for dev in red.devices.values():
        covered += [(max(float(s), red.t0), min(float(s) + float(d),
                                                red.t1))
                    for _, s, d in dev["modules"]]
    busy = sum(e - s for s, e in tracereduce._merge(covered) if e > s)
    return 100.0 * (1.0 - busy / 1e9 / red.window_s)


def per_request(run: Any, name: str) -> Optional[float]:
    """Spans named ``name`` that start inside the window, per request."""
    red = run.reduction
    n = sum(1 for k, s, _ in red.host if k == name and red.t0 <= s < red.t1)
    if not n or not run.requests:
        return None
    return n / len(run.requests)


# the readers the metric files name, one per layer

def host_flow(run: Any) -> Optional[float]:
    """Flow compile (every ``repro.flow.<pass>``), % of the window."""
    return self_share(run, "repro.flow.")


def codegen_per_request(run: Any) -> Optional[float]:
    """Codegen runs per request."""
    return per_request(run, "repro.flow.codegen")


def host_prep(run: Any) -> Optional[float]:
    """Decode prep, % of the window."""
    return self_share(run, "repro.sim.decode_prep")


def host_device_call(run: Any) -> Optional[float]:
    """Stage-pass device calls seen from the host (upload, dispatch,
    wait, download), % of the window."""
    return self_share(run, "repro.sim.device_call")


def host_finish(run: Any) -> Optional[float]:
    """Host finish of the device's decode, % of the window."""
    return self_share(run, "repro.sim.finish")


def host_replay(run: Any) -> Optional[float]:
    """Boundary-op replay, % of the window."""
    return self_share(run, "repro.sim.replay")


def host_forward(run: Any) -> Optional[float]:
    """The Pallas-backed forward pass's own host work, % of the
    window."""
    return self_share(run, "repro.ref.forward")


def host_kernel_call(run: Any) -> Optional[float]:
    """Kernel calls seen from the host (conversion, upload, kernel,
    download), % of the window."""
    return self_share(run, "repro.kernel.cim_mvm")


def host_oracle(run: Any) -> Optional[float]:
    """The numpy oracle and its comparison, % of the window."""
    return self_share(run, "repro.ref.oracle")


def host_fleet(run: Any) -> Optional[float]:
    """The fleet's own work outside its nested layers (grouping,
    machine models, aggregation, releasing the replay plans), % of the
    window."""
    return self_share(run, "repro.explore.fleet")
