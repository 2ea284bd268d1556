"""Run one cell once: set-up, a closed-loop window, the check, the line.

:func:`run_cell` does every step of a run after the command line:

1. find the cell's files (:mod:`registry`) and the chips it asks for;
   without a TPU, or with fewer chips, it raises :class:`NoDevice` and
   nothing is printed on standard output;
2. set-up, counted in ``setup_s`` from the process's start: the entry
   compiles, makes its inputs from ``--seed`` and warms exactly the
   shapes the traffic uses;
3. the window: requests of the traffic mix, one at a time, until
   ``--seconds`` have passed; the window runs from the first request's
   start to the last one's end, and every compile inside it is counted;
4. after the window: the device's peak memory, then the reference check
   of what the window produced (not counted in any metric), then, with
   ``--trace 1``, the reduction of the profiler trace;
5. the result line on standard output, with every compared number
   beside its limit last in it and, before it, on standard error.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

import registry
import tracereduce
import traffic as traffic_mod

__all__ = ["NoDevice", "Run", "run_cell", "run_window", "device_info"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r}); "
                       f"this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Request:
    """One request of the window: what was sent, when, and what came
    back (or the error it raised)."""

    __slots__ = ("payload", "t0", "t1", "result", "error", "work")

    def __init__(self, payload: Dict[str, Any]) -> None:
        self.payload = payload
        self.t0 = self.t1 = 0.0
        self.result: Any = None
        self.error: Optional[str] = None
        self.work = 0.0

    @property
    def ok(self) -> bool:
        return self.error is None

    @property
    def latency_s(self) -> float:
        return self.t1 - self.t0


class Run:
    """Everything one run learned; the metric readers read it."""

    def __init__(self, cell: registry.Cell, seconds: float,
                 trace: bool) -> None:
        self.cell = cell
        self.seconds = seconds
        self.trace = trace
        self.setup_s = 0.0
        self.requests: List[Request] = []
        self.window_open = False
        self.window_compiles = 0
        self.reduction: Any = None
        self.entry: Any = None
        with open(os.path.join(registry.BENCH_DIR, "peaks.json")) as f:
            self.peaks_table = json.load(f)
        self.device: Dict[str, Any] = {}

    @property
    def peaks(self) -> Dict[str, float]:
        kind = self.device.get("kind")
        if kind not in self.peaks_table["devices"]:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           f"peaks.json")
        return self.peaks_table["devices"][kind]

    @property
    def span_s(self) -> float:
        """From the first request's start to the last one's end."""
        return self.requests[-1].t1 - self.requests[0].t0


def _compile_counter(run: Run) -> None:
    import jax

    def on_duration(event: str, secs: float, **kw: Any) -> None:
        if event == _COMPILE_EVENT and run.window_open:
            run.window_compiles += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def run_window(run: Run, entry: Any, rngs: Dict[str, Any]) -> None:
    import jax
    stream = traffic_mod.requests(run.cell.traffic, rngs["traffic"])
    run.window_open = True
    t_end = time.perf_counter() + run.seconds
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
        while True:
            req = Request(next(stream))
            with jax.profiler.TraceAnnotation(tracereduce.REQUEST):
                req.t0 = time.perf_counter()
                try:
                    req.result = entry.request(req.payload)
                except Exception as e:      # noqa: BLE001 - counted failed
                    req.error = f"{type(e).__name__}: {e}"
                    traceback.print_exc(file=sys.stderr)
                req.t1 = time.perf_counter()
            req.work = entry.work(req.payload)
            if req.ok:
                bad = entry.failed_in(req.result)
                if bad:
                    req.error = bad
            run.requests.append(req)
            if req.t1 >= t_end:
                break
    run.window_open = False


def _trace_dir(cell: registry.Cell) -> str:
    return os.path.join(cell.root, "bench_out", "trace", cell.name)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float,
             check_device: bool = True) -> Dict[str, Any]:
    """One run of one cell; returns the result line's object."""
    import jax

    cell = registry.Cell(root, workload)
    run = Run(cell, seconds, trace)
    if check_device:
        run.device = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        run.device = {"platform": d.platform, "kind": d.device_kind,
                      "count": cell.chips}
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program the cell runs goes to the persistent cache, the
    # short Pallas compiles too, so that only a checkout's first run
    # compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _compile_counter(run)

    rngs = traffic_mod.streams(seed)
    entry = cell.entry_module().Entry(cell, rngs)
    run.entry = entry
    entry.setup()
    run.setup_s = time.perf_counter() - t_start

    tdir = _trace_dir(cell)
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        run_window(run, entry, rngs)
    finally:
        if trace:
            jax.profiler.stop_trace()
    run.device["memory_peak_bytes"] = _peak_bytes(cell.chips)
    entry.release()

    checks = entry.check([r for r in run.requests if r.ok], rngs["check"])
    if trace:
        events = tracereduce.extract(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        # the extracted events, kept (one file per cell, overwritten) for
        # a reader who wants more than the breakdown
        with gzip.open(tdir + ".events.json.gz", "wt") as f:
            json.dump(events, f)
        run.reduction = tracereduce.Reduction(events)
    return _result(run, checks)


def _metric_values(run: Run, kind: str,
                   specs: List[Dict[str, Any]]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for spec in specs:
        mod = run.cell.metric_module(kind, spec["name"])
        v = mod.read(run)
        if v is not None:
            out[spec["name"]] = {"value": v, "unit": spec["unit"]}
    return out


def _result(run: Run, checks: List[Dict[str, Any]]) -> Dict[str, Any]:
    attempted = len(run.requests)
    failed = sum(1 for r in run.requests if not r.ok)
    correct = (attempted > 0 and failed == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks))
    out: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed}
    if run.trace:
        out["metrics"] = _metric_values(run, "metrics",
                                        run.cell.per_layer())
        red = run.reduction
        run.device["busy_s"] = red.busy_s()
        run.device["window_s"] = red.window_s
        out["device"] = run.device
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps(10)}
    else:
        out["metrics"] = _metric_values(run, "e2e", run.cell.end_to_end())
        out["device"] = run.device
    out["window_compiles"] = run.window_compiles
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out
