"""Structural sweeps: every design point compiled afresh, then
simulated.

A request carries ``points_per_request`` distinct points of
``mg_flit_space`` (macros per group x flit width x partition strategy),
the grid an architect sweeps when the chip's structure changes: unlike
a timing sweep, no two points share a compiled program.  The entry
takes them, in place of the generator's independent draws, from one
seeded permutation of the whole grid, consecutive requests taking
consecutive points and starting over at its end: with 4 of 12 points,
every 3 requests cover the grid once, so a window's work does not
hinge on which points a seed drew (points differ in cost threefold).
The payload is rewritten to the points served, which the check reads.
Each point runs the compile layer
users pay for (the partition search and codegen for that chip; the
condensed graph is built once at set-up) through a fresh
``flow.Pipeline`` per request, so no pass cache and no result cache
serves it, then ``Simulator(chip, isa, engine="jax").run_model`` (one
single-machine stage pass per stage, host finish and replay).

Set-up compiles and simulates every point of the grid once, so each
stage-pass bucket the window can meet is compiled.  The window keeps,
per point, its statistics and a digest of its compiled listing; the
check recompiles one point from each of ``check_sample`` equal ranges
of the window's points, requires the same listing, and re-runs it on
:mod:`refsim` with the point's structure put over the configuration's
chip.
"""

from __future__ import annotations

import hashlib
import itertools
from typing import Any, Dict, List, Optional

import numpy as np

import simcheck


def _digest(cm: Any) -> str:
    """Digest of a compiled model's per-stage, per-core instruction
    tables."""
    h = hashlib.sha256()
    for sp in cm.stages:
        for cid in sorted(sp.programs):
            pk = sp.programs[cid].pack(cm.isa)
            h.update(np.int64(cid).tobytes())
            h.update(np.ascontiguousarray(pk.op).tobytes())
            for name in sorted(pk.args):
                h.update(name.encode())
                h.update(np.ascontiguousarray(pk.args[name]).tobytes())
        h.update(b"|")
    return h.hexdigest()


class Entry:
    def __init__(self, cell: Any, rngs: Dict[str, np.random.Generator]
                 ) -> None:
        self.cfg = cell.config
        self.mix = cell.traffic
        self.rngs = rngs
        self.cg: Any = None
        self.params: Any = None
        draws = self.mix["draws"]
        fields = sorted(draws)
        self.grid = [dict(zip(fields, v)) for v in
                     itertools.product(*(draws[f] for f in fields))]
        # the traffic's permutation of the grid, drawn before the
        # window's stream draws from the same generator
        self.order = [int(i) for i in
                      rngs["traffic"].permutation(len(self.grid))]
        self.served = 0

    def _compile(self, point: Dict[str, Any]) -> Any:
        """One point through a pipeline of its own: partition, codegen."""
        from repro.explore import DesignPoint
        from repro.flow import CompileOptions
        from repro.flow.pipeline import Pipeline
        pt = DesignPoint(**point)
        art = Pipeline().compile(self.cg, pt.chip(), CompileOptions(
            strategy=pt.strategy, params=self.params, fidelity="simulate"))
        return art.ensure_model()

    def _simulate(self, point: Dict[str, Any], cm: Any) -> Dict[str, Any]:
        from repro.core.simulator import Simulator
        from repro.explore import DesignPoint
        rep = Simulator(DesignPoint(**point).chip(), cm.isa,
                        engine="jax").run_model(cm)
        return {"cycles": rep.cycles, "energy": dict(rep.energy()),
                "listing": _digest(cm)}

    def setup(self) -> None:
        from repro.core import workloads
        from repro.core.mapping import CostParams

        self.params = CostParams(batch=self.cfg["batch"])
        self.cg = workloads.build(self.cfg["model"],
                                  **self.cfg["workload_kw"]).condense()
        for point in self.grid:
            self._simulate(point, self._compile(point))

    def request(self, payload: Dict[str, Any]) -> List[Dict[str, Any]]:
        n = len(payload["points"])
        payload["points"] = [
            self.grid[self.order[(self.served + i) % len(self.order)]]
            for i in range(n)]
        self.served += n
        return [self._simulate(p, self._compile(p))
                for p in payload["points"]]

    def work(self, payload: Dict[str, Any]) -> float:
        return float(len(payload["points"]))

    def failed_in(self, recs: List[Dict[str, Any]]) -> Optional[str]:
        return None

    def release(self) -> None:
        pass

    def check(self, done: List[Any], rng: np.random.Generator,
              control: bool = False) -> List[Dict[str, Any]]:
        if not done:
            return []
        answers = [(p, rec) for req in done
                   for p, rec in zip(req.payload["points"], req.result)]
        got, want = [], []
        for i in simcheck.stratified(len(answers),
                                     int(self.mix["check_sample"]), rng):
            point, rec = answers[i]
            cm = self._compile(point)
            timing = {k: v for k, v in point.items() if k != "strategy"}
            ref = simcheck.reference(simcheck.listing(cm), self.cfg, timing)
            if control:
                got.append(simcheck.reference(simcheck.listing(cm),
                                              self.cfg, timing,
                                              control=True))
            elif _digest(cm) != rec["listing"]:
                # the window ran another program: an infinite gap
                got.append({"cycles": float("inf"), "energy": {}})
            else:
                got.append({"cycles": rec["cycles"],
                            "energy": rec["energy"]})
            want.append(ref)
        return simcheck.check(got, want, self.mix["limits"])
