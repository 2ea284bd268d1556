"""Timing sweeps: ``ExplorationEngine(engine="jax").evaluate(points,
fidelity="simulate")`` on distinct timing-only design points.

All points of a request share the configuration's structural chip, so
the engine evaluates them as one fleet: one pinned program, one
vmapped device call per stage (the stage pass, ``jit_stage_pass``),
then a host finish and replay per machine.  The result cache is off, so
every point is new to the engine.

Set-up compiles the pinned program and warms the fleet's device
program on each stage's shape with a machine batch of the request's
size.  The check re-runs sampled points on :mod:`refsim`: one point from
each of ``check_sample`` equal ranges of the request's positions, from
a request drawn at random, so a fleet that drops part of its batch is
seen.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import simcheck
import traffic


class Entry:
    def __init__(self, cell: Any, rngs: Dict[str, np.random.Generator]
                 ) -> None:
        self.cfg = cell.config
        self.mix = cell.traffic
        self.rngs = rngs
        self.flow_cache = os.path.join(cell.root, "bench_out", "flow_cache")
        self.engine: Any = None
        self.cm: Any = None
        self._rows: Optional[List[int]] = None

    def _points(self, payload: Dict[str, Any]) -> List[Any]:
        from repro.explore import DesignPoint
        return [DesignPoint(strategy=self.cfg["strategy"], **p)
                for p in payload["points"]]

    def setup(self) -> None:
        from repro import flow
        from repro.core.jaxsim import FleetStageDecoder
        from repro.core.machine import machine_for
        from repro.core.mapping import CostParams
        from repro.explore import ExplorationEngine, canonical_chip
        from repro.flow import CompileOptions

        params = CostParams(batch=self.cfg["batch"])
        self.engine = ExplorationEngine(
            self.cfg["model"], params=params, engine="jax", cache=None,
            flow_cache=self.flow_cache, **self.cfg["workload_kw"])
        pts = self._points(traffic.draw_request(self.mix,
                                                  self.rngs["setup"]))
        art = flow.compile(self.engine.cg, canonical_chip(pts[0].chip()),
                           CompileOptions(strategy=self.cfg["strategy"],
                                          params=params,
                                          fidelity="simulate"))
        self.cm = art.ensure_model()
        dec = FleetStageDecoder(self.cm.isa,
                                [machine_for(p.chip()) for p in pts])
        for sp in self.cm.stages:
            dec.decode_stage(sp.programs)

    def request(self, payload: Dict[str, Any]) -> List[Any]:
        return self.engine.evaluate(self._points(payload),
                                    fidelity="simulate")

    def work(self, payload: Dict[str, Any]) -> float:
        return float(len(payload["points"]))

    def failed_in(self, recs: List[Any]) -> Optional[str]:
        bad = [r.error for r in recs if not r.ok]
        return f"{len(bad)} points failed: {bad[0]}" if bad else None

    def release(self) -> None:
        self.engine = None

    def stage_calls(self) -> List[Tuple[int, int]]:
        """Each request's stage-pass device calls: (decode rows,
        machines)."""
        if self._rows is None:
            self._rows = simcheck.decode_rows(self.cm)
        n = int(self.mix["points_per_request"])
        return [(r, n) for r in self._rows]

    def check(self, done: List[Any], rng: np.random.Generator,
              control: bool = False) -> List[Dict[str, Any]]:
        if not done:
            return []
        prog = simcheck.listing(self.cm)
        per = int(self.mix["points_per_request"])
        got, want = [], []
        for pos in simcheck.stratified(per, int(self.mix["check_sample"]),
                                       rng):
            req = done[int(rng.integers(len(done)))]
            timing = req.payload["points"][pos]
            ref = simcheck.reference(prog, self.cfg, timing)
            if control:
                ctl = simcheck.reference(prog, self.cfg, timing,
                                         control=True)
                got.append({"cycles": ctl["cycles"],
                            "energy": ctl["energy"]})
            else:
                rec = req.result[pos]
                got.append({"cycles": rec.cycles,
                            "energy": dict(rec.energy)})
            want.append(ref)
        return simcheck.check(got, want, self.mix["limits"])
