#!/usr/bin/env python3
"""Smoke run of the CIM design-point path on one TPU chip.

    python3 chip_smoke.py

Drives resnet18 at 224x224 (strategy ``dp``, ``CostParams(batch=4)``,
``default_chip()``) through the entry points a user calls, in one
process on one chip, phase by phase:

* ``device``   -- JAX must find a TPU; on any other platform the script
  exits non-zero and never falls back to the CPU;
* ``kernel``   -- ``kernels.ops.cim_mvm`` (native Pallas, interpret mode
  off) on a resnet18@224 layer shape, bit-equal to an int64 numpy matmul;
* ``simulate`` -- ``Simulator(engine="jax")`` against ``engine="vector"``
  on the compiled model: cycles, stage_cycles, events, unit_busy and
  instrs equal, and every stage decoded by one device call;
* ``fleet``    -- ``ExplorationEngine(engine="jax")`` over the 64
  ``timing_space()`` points, one vmapped device call per stage, four
  points re-run on the vector engine over the same pinned program;
* ``pallas``   -- ``evaluate("func:pallas")`` at batch 1, every group
  bit-exact against the numpy oracle.

Each phase prints one line with its wall time on the chip machine's
host clock, and the XLA compile time inside it where it compiles
(compile times are read from JAX's own compile events, so a persistent
cache hit shows as a short retrieval).  The simulate and fleet phases
run under ``jax.profiler`` (python tracer off), and their stage-pass
device calls are read from the program's own ``repro.sim.device_call``
spans, whose counters give each call's decode rows, bucket, register
bucket and machines (the traces stay in ``results/chip_smoke_trace/``
for TensorBoard or Perfetto); the stage-pass compiles are listed per
decode-table bucket.  The persistent compile cache sits in
``$JAX_COMPILATION_CACHE_DIR`` or ``results/jax_cache`` (see
``repro.compile_cache``), so a second run skips them.

These are smoke results of a bring-up check, not benchmark metrics.
Any failure raises and exits non-zero; on success the last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                               # noqa: E402

from repro import flow                                   # noqa: E402
from repro.compile_cache import enable_compile_cache     # noqa: E402
from repro.core.arch import default_chip                 # noqa: E402
from repro.core.mapping import CostParams                # noqa: E402
from repro.core.simulator import Simulator               # noqa: E402
from repro.explore import (ExplorationEngine, canonical_chip,  # noqa: E402
                           timing_space)
from repro.flow import CompileOptions                    # noqa: E402
from repro.kernels import ops                            # noqa: E402

MODEL = "resnet18"
RES = 224
BATCH = 4
STRATEGY = "dp"
# resnet18@224 layer2's 3x3 conv as an im2col matmul at batch 4:
# (28*28*4 rows x 3*3*128) @ (3*3*128 x 128); 3136 rows pad to 3200
KERNEL_SHAPE = (3136, 1152, 128)
FLEET_CHECKS = (0, 21, 42, 63)          # timing_space() indices re-run
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
DEVICE_CALL = "repro.sim.device_call"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """XLA compiles seen through ``jax.monitoring``, in order.

    Each entry is ``(function name, seconds, persistent-cache hit)``;
    the hit event fires inside the compile it belongs to, before that
    compile's duration is recorded.
    """

    def __init__(self) -> None:
        self.entries: List[Tuple[str, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == _CACHE_HIT_EVENT:
            self._hit = True

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == _COMPILE_EVENT:
            self.entries.append((str(kw.get("fun_name", "?")), secs,
                                 self._hit))
            self._hit = False

    def since(self, mark: int) -> List[Tuple[str, float, bool]]:
        return self.entries[mark:]


def _phase_line(name: str, wall: float, compiles, extra: str = "") -> None:
    comp = sum(s for _, s, _ in compiles)
    hits = sum(1 for _, _, h in compiles if h)
    print(f"phase {name}: chip wall {wall:.3f} s "
          f"(XLA compile {comp:.3f} s over {len(compiles)} compiles, "
          f"{hits} persistent-cache hits; rest {wall - comp:.3f} s)"
          + (f"; {extra}" if extra else ""), flush=True)


def phase_device() -> Dict[str, Any]:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{dev.platform!r}); this check runs only on a TPU",
              file=sys.stderr, flush=True)
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def phase_kernel(log: CompileLog) -> None:
    _check(ops._auto_interpret() is False,
           "Pallas would run in interpret mode on this backend")
    m, k, n = KERNEL_SHAPE
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, (m, k), dtype=np.int8)
    w = rng.integers(-128, 128, (k, n), dtype=np.int8)
    mark = len(log.entries)
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(ops.cim_mvm(x, w)))
    first = time.perf_counter() - t0
    t1 = time.perf_counter()
    jax.block_until_ready(ops.cim_mvm(x, w))
    second = time.perf_counter() - t1
    want = x.astype(np.int64) @ w.astype(np.int64)
    _check(got.shape == want.shape and got.dtype == np.int32,
           f"cim_mvm returned {got.shape} {got.dtype}")
    _check(np.array_equal(got.astype(np.int64), want),
           "cim_mvm differs from the int64 numpy matmul")
    _phase_line("kernel", first, log.since(mark),
                f"({m}x{k})@({k}x{n}) bit-exact; second call "
                f"{second:.6f} s")


@contextlib.contextmanager
def device_calls(phase: str) -> Iterator[List[Tuple[int, int, int, int]]]:
    """Trace the body and fill the list it binds, after it, with every
    stage-pass device call the program made in it, in order, as
    ``(decode rows, padded bucket, register bucket, machines)`` read
    from the ``repro.sim.device_call`` spans' counters."""
    from jax.profiler import ProfileData

    trace_dir = os.path.join(ROOT, "results", "chip_smoke_trace", phase)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    calls: List[Tuple[int, int, int, int]] = []
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield calls
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    _check(bool(files), f"{phase}: the profiler wrote no trace")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    spans = sorted(((e.start_ns, dict(e.stats))
                    for plane in pd.planes if plane.name == "/host:CPU"
                    for line in plane.lines for e in line.events
                    if e.name == DEVICE_CALL), key=lambda x: x[0])
    calls.extend(tuple(int(st[k]) for k in ("rows", "bucket", "regs",
                                            "machines"))
                 for _, st in spans)


def _bucket_report(label: str, calls, compiles) -> None:
    """One stage-pass compile per new ``(bucket, registers)`` shape,
    cold or a persistent-cache hit, each printed."""
    keys: List[Tuple[int, int]] = []
    for _, nb, nregs, _ in calls:
        if (nb, nregs) not in keys:
            keys.append((nb, nregs))
    passes = [c for c in compiles if "stage_pass" in c[0]]
    _check(len(passes) == len(keys),
           f"{label}: {len(passes)} stage-pass compiles for "
           f"{len(keys)} buckets")
    for (nb, nregs), (_, secs, hit) in zip(keys, passes):
        print(f"  {label} stage pass bucket {nb} x {nregs}: "
              f"compile {secs:.3f} s "
              f"({'persistent-cache hit' if hit else 'cold'})",
              flush=True)


def _same_report(label: str, jx: Any, vec: Any) -> None:
    for field in ("cycles", "stage_cycles", "events", "unit_busy",
                  "instrs"):
        a, b = getattr(jx, field), getattr(vec, field)
        _check(a == b, f"{label}: jax engine {field} {a!r} != vector "
                       f"engine {b!r}")


def phase_simulate(log: CompileLog) -> None:
    chip = default_chip()
    t0 = time.perf_counter()
    art = flow.compile(MODEL, chip, CompileOptions(
        strategy=STRATEGY, params=CostParams(batch=BATCH),
        workload_kw={"res": RES}, fidelity="simulate"))
    cm = art.ensure_model()
    host_compile = time.perf_counter() - t0

    mark = len(log.entries)
    with device_calls("simulate") as calls:
        t1 = time.perf_counter()
        jx = Simulator(chip, cm.isa, engine="jax").run_model(cm)
        jax_first = time.perf_counter() - t1
    compiles = log.since(mark)
    for i, (n, nb, nregs, _) in enumerate(calls):
        print(f"  device call {i}: {n} decode rows -> bucket {nb} x "
              f"{nregs}", flush=True)
    # run_model decodes each stage once: one device call per stage, or
    # a stage took the scalar fallback
    _check(len(calls) == len(cm.stages),
           f"{len(calls)} device calls for {len(cm.stages)} stages")
    t2 = time.perf_counter()
    Simulator(chip, cm.isa, engine="jax").run_model(cm)
    jax_warm = time.perf_counter() - t2
    t3 = time.perf_counter()
    vec = Simulator(chip, cm.isa, engine="vector").run_model(cm)
    vec_wall = time.perf_counter() - t3
    _same_report("simulate", jx, vec)
    _phase_line("simulate", jax_first, compiles,
                f"{len(calls)}/{len(cm.stages)} stages decoded on the "
                f"device; jax == vector in cycles ({jx.cycles:.0f}), "
                f"stage_cycles, events, unit_busy, instrs "
                f"({jx.instrs}); host compile {host_compile:.3f} s, "
                f"jax engine in-process rerun {jax_warm:.3f} s, "
                f"vector engine {vec_wall:.3f} s")
    _bucket_report("single", calls, compiles)


def phase_fleet(log: CompileLog) -> None:
    points = list(timing_space().points())
    _check(len(points) == 64, f"timing_space() has {len(points)} points")
    canon = canonical_chip(points[0].chip())
    _check(all(canonical_chip(p.chip()) == canon for p in points),
           "timing_space() spans more than one compiled program")
    params = CostParams(batch=BATCH)
    eng = ExplorationEngine(MODEL, params=params, engine="jax", res=RES)
    mark = len(log.entries)
    with device_calls("fleet") as calls:
        t0 = time.perf_counter()
        recs = eng.evaluate(points, fidelity="simulate")
        wall = time.perf_counter() - t0
    compiles = log.since(mark)
    bad = [r.error for r in recs if not r.ok]
    _check(not bad, f"fleet: {len(bad)} records failed, first: "
                    f"{bad[:1]}")
    # the pinned-program contract: each point equals the vector engine
    # run of its own machine over the group's canonical compiled model
    t1 = time.perf_counter()
    art = flow.compile(eng.cg, canon,
                       CompileOptions(strategy=STRATEGY, params=params,
                                      fidelity="simulate"))
    cm = art.ensure_model()
    _check(len(calls) == len(cm.stages)
           and all(c[3] == len(points) for c in calls),
           f"fleet: device calls {calls} for {len(cm.stages)} stages of "
           f"{len(points)} machines (a stage fell back to the scalar "
           f"interpreter)")
    for i in FLEET_CHECKS:
        chip = points[i].chip()
        rep = Simulator(chip, cm.isa, engine="vector").run_model(cm)
        _check(recs[i].cycles == rep.cycles,
               f"fleet point {i}: cycles {recs[i].cycles} != vector "
               f"{rep.cycles}")
        _check(recs[i].energy == dict(rep.energy()),
               f"fleet point {i}: energy differs from the vector engine")
    check_wall = time.perf_counter() - t1
    cyc = [r.cycles for r in recs]
    _phase_line("fleet", wall, compiles,
                f"64/64 records ok, {len(calls)} vmapped device calls "
                f"(one per stage), cycles {min(cyc):.0f}..{max(cyc):.0f}; "
                f"points {list(FLEET_CHECKS)} equal the vector engine "
                f"({check_wall:.3f} s)")
    _bucket_report("fleet", calls, compiles)


def phase_pallas(log: CompileLog) -> None:
    art = flow.compile(MODEL, default_chip(), CompileOptions(
        strategy=STRATEGY, params=CostParams(batch=1),
        workload_kw={"res": RES}, fidelity="simulate"))
    mark = len(log.entries)
    t0 = time.perf_counter()
    rep = art.evaluate("func:pallas", check=True)
    wall = time.perf_counter() - t0
    n = len(rep.outputs or {})
    _check(n == len(art.cg.groups) and n > 0,
           f"func:pallas checked {n} groups of {len(art.cg.groups)}")
    _phase_line("pallas", wall, log.since(mark),
                f"{n} groups bit-exact vs the numpy oracle "
                f"(interpret={ops._auto_interpret()})")


def main() -> int:
    t0 = time.perf_counter()
    device = phase_device()
    print(f"phase device: {device['kind']} x{device['count']} "
          f"(chip wall {time.perf_counter() - t0:.3f} s)", flush=True)
    cache_dir = enable_compile_cache()
    n_cached = (len(os.listdir(cache_dir))
                if os.path.isdir(cache_dir) else 0)
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)",
          flush=True)
    log = CompileLog()
    phase_kernel(log)
    phase_simulate(log)
    phase_fleet(log)
    phase_pallas(log)
    print(f"total chip wall {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
