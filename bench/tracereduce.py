"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second is tested on a small recorded
trace without a chip:

1. :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
   and keeps three kinds of events, as ``[name, start_ns, duration_ns]``:
   each device's program executions (``XLA Modules``) and operations
   (``XLA Ops``), and the events of the host thread that carries the
   benchmark's own annotations (``bench.window``, ``bench.request``).
2. :class:`Reduction` computes from those: the device's busy time (the
   union of its program executions inside the window), its idle share,
   the device time of the programs or kernels a metric names, the
   operations that took most time, and the longest idle gaps, each
   labelled with the innermost host event around it.

Program executions are found by the jitted function's name (the stage
pass is ``jit_stage_pass``, the Pallas kernel's wrapper ``jit_cim_mvm``)
and a kernel as the ``custom-call`` operations inside its wrapper.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["extract", "Reduction", "WINDOW", "REQUEST"]

WINDOW = "bench.window"
REQUEST = "bench.request"

Event = List[Any]          # [name, start_ns, duration_ns]


def extract(trace_dir: str) -> Dict[str, Any]:
    """The events a :class:`Reduction` needs, from the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            devices[plane.name[len("/device:"):]] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
                if any(e[0] == WINDOW for e in evs):
                    host = evs
    return {"devices": devices, "host": host}


def _merge(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def _short(op: str) -> str:
    """``%fusion.12 = u32[...] fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


class Reduction:
    """Numbers of one traced window."""

    def __init__(self, events: Dict[str, Any]) -> None:
        self.devices = events["devices"]
        self.host = events["host"]
        win = [e for e in self.host if e[0] == WINDOW]
        if not win:
            raise ValueError(f"trace holds no {WINDOW!r} annotation")
        _, s, d = win[0]
        self.t0, self.t1 = float(s), float(s) + float(d)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _busy(self, dev: Dict[str, List[Event]]) -> List[Tuple[float, float]]:
        return _merge([(s, s + d) for _, s, d in dev["modules"]])

    def busy_s(self) -> float:
        """Seconds in which a program ran on the device, inside the
        window, averaged over the devices traced."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for dev in self.devices.values():
            tot += sum(_clip(s, e, self.t0, self.t1)
                       for s, e in self._busy(dev))
        return tot / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def _in_window(self, s: float, d: float) -> bool:
        return s >= self.t0 and s + d <= self.t1

    def module_events(self, prefix: str) -> List[Event]:
        """Executions of the jitted programs whose name starts with
        ``prefix``, inside the window, on every device."""
        return [e for dev in self.devices.values() for e in dev["modules"]
                if e[0].startswith(prefix) and self._in_window(e[1], e[2])]

    def module_s(self, prefix: str) -> float:
        return sum(e[2] for e in self.module_events(prefix)) / 1e9

    def kernel_events(self, module_prefix: str) -> List[Event]:
        """The ``custom-call`` operations (a Pallas kernel) that run
        inside executions of the programs named ``module_prefix``."""
        out: List[Event] = []
        for dev in self.devices.values():
            mods = sorted((s, s + d) for n, s, d in dev["modules"]
                          if n.startswith(module_prefix)
                          and self._in_window(s, d))
            for ev in dev["ops"]:
                name, s, d = ev
                if "custom-call" not in name:
                    continue
                if any(ms <= s and s + d <= me for ms, me in mods):
                    out.append(ev)
        return out

    def kernel_s(self, module_prefix: str) -> float:
        return sum(e[2] for e in self.kernel_events(module_prefix)) / 1e9

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        """Device operations that took most time in the window (summed
        over their executions), as ``[name, seconds]``."""
        tot: Dict[str, float] = {}
        for dev in self.devices.values():
            for name, s, d in dev["ops"]:
                if self._in_window(s, d):
                    key = _short(name)
                    tot[key] = tot.get(key, 0.0) + d
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def _label(self, t: float) -> str:
        """The innermost host event that covers ``t`` (of two that last
        as long, the later one, which opened inside the other)."""
        best: Optional[Event] = None
        for ev in self.host:
            name, s, d = ev
            if s <= t <= s + d and (best is None or d <= best[2]):
                best = ev
        return best[0] if best is not None else "no host event"

    def idle_gaps(self, n: int = 10) -> List[List[Any]]:
        """The longest stretches of the window in which no program ran
        on the device, as ``[host event around it, seconds]``."""
        gaps: List[Tuple[float, float]] = []
        for dev in self.devices.values():
            t = self.t0
            for s, e in self._busy(dev):
                if s > t:
                    gaps.append((t, min(s, self.t1)))
                t = max(t, e)
            if t < self.t1:
                gaps.append((t, self.t1))
        gaps = [(s, e) for s, e in gaps if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._label((s + e) / 2), (e - s) / 1e9]
                for s, e in gaps[:n]]
