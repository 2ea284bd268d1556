"""Single design points: ``Simulator(chip, isa, engine="jax")
.run_model(cm)`` on one timing point at a time.

The interactive what-if and a sequential search send such requests.
Each runs the single-machine stage pass (``jit_stage_pass``, donated
buffers), one device call per stage, then the host finish and replay:
the fleet's vmap never amortizes anything here.  Set-up compiles the
program once for the configuration's chip and runs one warm-up point.
The check re-runs requests sampled from ``check_sample`` equal ranges
of the window on :mod:`refsim` and compares every statistic of the
report.
"""

from __future__ import annotations

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
        self.cm: Any = None
        self._rows: Optional[List[int]] = None

    def _chip(self, timing: Dict[str, Any]) -> Any:
        from repro.explore import DesignPoint
        return DesignPoint(strategy=self.cfg["strategy"], **timing).chip()

    def setup(self) -> None:
        from repro import flow
        from repro.core.arch import default_chip
        from repro.core.mapping import CostParams
        from repro.flow import CompileOptions

        art = flow.compile(self.cfg["model"], default_chip(), CompileOptions(
            strategy=self.cfg["strategy"],
            params=CostParams(batch=self.cfg["batch"]),
            workload_kw=self.cfg["workload_kw"], fidelity="simulate"))
        self.cm = art.ensure_model()
        warm = traffic.draw_request(self.mix, self.rngs["setup"])
        for timing in warm["points"]:
            self.request({"points": [timing]})

    def request(self, payload: Dict[str, Any]) -> List[Any]:
        from repro.core.simulator import Simulator
        return [Simulator(self._chip(t), self.cm.isa, engine="jax")
                .run_model(self.cm) for t in payload["points"]]

    def work(self, payload: Dict[str, Any]) -> float:
        return float(len(payload["points"]))

    def failed_in(self, reps: List[Any]) -> Optional[str]:
        return None

    def release(self) -> None:
        pass

    def stage_calls(self) -> List[Tuple[int, int]]:
        """Each request's stage-pass device calls: (decode rows,
        machines)."""
        if self._rows is None:
            self._rows = simcheck.decode_rows(self.cm)
        n = int(self.mix["points_per_request"])
        return [(r, 1) for r in self._rows] * n

    def check(self, done: List[Any], rng: np.random.Generator,
              control: bool = False) -> List[Dict[str, Any]]:
        if not done:
            return []
        prog = simcheck.listing(self.cm)
        got, want = [], []
        for i in simcheck.stratified(len(done), int(self.mix["check_sample"]),
                                     rng):
            req = done[i]
            for timing, rep in zip(req.payload["points"], req.result):
                ref = simcheck.reference(prog, self.cfg, timing)
                if control:
                    got.append(simcheck.reference(prog, self.cfg, timing,
                                                  control=True))
                else:
                    got.append({"cycles": rep.cycles,
                                "stage_cycles": list(rep.stage_cycles),
                                "events": dict(rep.events),
                                "unit_busy": dict(rep.unit_busy),
                                "instrs": rep.instrs,
                                "energy": dict(rep.energy())})
                want.append(ref)
        return simcheck.check(got, want, self.mix["limits"])
