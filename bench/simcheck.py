"""What the simulate cells compare: a run's statistics against
:mod:`refsim` on the same compiled program and machine.

The compiled program is the simulator's input, made at set-up by the
system's compiler; :func:`listing` hands it to the reference as plain
data.  Each compared number is the widest relative gap over the
sampled answers; the statistics are exact on both sides (integer and
dyadic sums), so sound runs read 0.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Sequence, Tuple

import numpy as np

import refsim

__all__ = ["listing", "decode_rows", "reference", "gap", "stratified", "check"]


def listing(cm: Any) -> List[List[Tuple[int, List[Tuple[str, Dict]]]]]:
    """A compiled model's per-stage, per-core instruction streams as
    ``(op, args)`` tuples: special registers by name, the int8 flag of a
    vector op as ``i8``."""
    from repro.core.isa import FLAGS, SREG
    names = {v: k for k, v in SREG.items()}
    i8 = FLAGS["i8"]
    out = []
    for sp in cm.stages:
        progs = []
        for cid, prog in sp.programs.items():
            ins = []
            for i in prog.instrs:
                a = dict(i.args)
                if "sreg" in a:
                    a["sreg"] = names[a["sreg"]]
                if i.op.startswith("V_"):
                    a["i8"] = bool(a.get("flags", 0) & i8)
                ins.append((i.op, a))
            progs.append((cid, ins))
        out.append(progs)
    return out


def decode_rows(cm: Any) -> List[int]:
    """Each stage's decode rows: what one stage-pass device call
    computes on, before padding to its bucket."""
    from repro.core.arch import default_chip
    from repro.core.jaxsim import FleetStageDecoder
    from repro.core.machine import machine_for
    dec = FleetStageDecoder(cm.isa, [machine_for(default_chip())])
    return [dec.prep(sp.programs).n for sp in cm.stages]


def reference(prog: Any, cfg: Dict[str, Any], timing: Dict[str, Any],
              control: bool = False) -> Dict[str, Any]:
    """The reference's statistics of one design point; ``control``
    computes them in float32 instead of float64."""
    m = refsim.machine_constants(cfg["chip"], timing)
    return refsim.simulate(prog, m, cfg["energy_nj"],
                           time_dtype=np.float32 if control else np.float64)


def gap(a: float, b: float) -> float:
    """Relative gap of ``a`` from the reference value ``b``."""
    if a == b:
        return 0.0
    return abs(a - b) / abs(b) if b else float("inf")


def widest(pairs: Iterable[Tuple[float, float]]) -> float:
    return max((gap(a, b) for a, b in pairs), default=0.0)


def stratified(n: int, k: int, rng: np.random.Generator) -> List[int]:
    """``k`` of ``range(n)``: one drawn from each of ``k`` equal
    contiguous strata (all of them when ``n <= k``)."""
    if n <= k:
        return list(range(n))
    edges = np.linspace(0, n, k + 1).astype(int)
    return [int(rng.integers(lo, hi)) for lo, hi in zip(edges, edges[1:])]


def check(got: Sequence[Dict[str, Any]], want: Sequence[Dict[str, Any]],
          limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Compare answers with reference answers, field by field.

    Each answer is a dict with ``cycles``, ``energy`` (category -> nJ)
    and, where the entry returns them, ``stage_cycles``, ``events``,
    ``unit_busy`` and ``instrs``.  A field missing on one side reads as
    an infinite gap."""
    def pairs(key: str):
        for g, w in zip(got, want):
            if key not in g:
                continue
            a, b = g[key], w[key]
            if isinstance(b, dict):
                for k in set(a) | set(b):
                    yield a.get(k, float("inf")), b.get(k, 0.0)
            elif isinstance(b, list):
                if len(a) != len(b):
                    yield float("inf"), 1.0
                yield from zip(a, b)
            else:
                yield a, b

    numbers = {"cycles_gap": widest(pairs("cycles")),
               "energy_gap": widest(pairs("energy"))}
    if "stats_gap" in limits:
        numbers["stats_gap"] = max(
            widest(pairs(k)) for k in ("stage_cycles", "events",
                                       "unit_busy", "instrs"))
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in numbers.items()]
