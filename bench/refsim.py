"""Plain reference of the CIM chip's perf-mode timing semantics.

Independent of the code under test: it imports nothing of ``repro``.
It reads a compiled program as plain data (per stage, per core, a list
of ``(op, args)`` with special registers named and the int8 flag as a
boolean) and a machine as a dict of published constants (the
configuration's ``chip`` block plus the design point's timing fields),
and steps the cores one instruction at a time:

* the core with the earliest local time issues next (ties: program
  order); issue is in order, one cycle apart, and each execution unit
  (scalar, vector, cim, noc) is a pipeline that is busy for the
  instruction's latency;
* a CIM MVM of ``rep`` vectors takes ``rep * act_bits + tree depth``
  cycles; a weight load ``rows / rows_per_cycle``; a vector op
  ``ceil(n / lanes)`` beats plus the ALU or multiplier latency (LUT ops:
  beats times the LUT latency);
* SEND reserves every link of its XY route (wormhole: a link is held
  for ``ceil(bytes / flit) / flits_per_cycle`` cycles, each hop adds the
  router latency); RECV waits for the matching message on its channel
  and blocks (re-issuing later, each attempt counted) while none has
  arrived;
* GLD / GST stream over the earliest-free global-memory port;
* every instruction adds its energy events to one ledger.

The result is what a user reads from a simulate run: total and
per-stage cycles, the event ledger, unit busy cycles, the instruction
count and the energy breakdown.

``time_dtype=np.float32`` rounds every time, busy sum and ledger sum to
float32: the lower precision a later change might be tempted to compute
in.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

__all__ = ["machine_constants", "simulate", "energy_breakdown"]

# vector ops priced on the multiplier or the LUT pipeline; every other
# vector op is an ALU op
VEC_MUL = frozenset({"mul", "mac", "muli", "quant", "dequant"})
VEC_LUT = frozenset({"sigmoid", "silu", "gelu", "tanh", "exp", "recip",
                     "rsqrt", "softmax", "layernorm"})

# event ledger key -> (energy category, nJ per event key in the table)
_ENERGY = {
    "cim_macro_passes": ("compute", "cim_macro_pass"),
    "cim_weight_load_bytes": ("weight_load", "cim_weight_load_byte"),
    "vector_elems": ("compute", "vector_elem"),
    "noc_byte_hops": ("noc", "noc_byte_hop"),
    "gmem_bytes": ("gmem", "gmem_byte"),
    "lmem_bytes": ("lmem", "lmem_byte"),
    "static_core_cycles": ("static", "static_core_cycle"),
}


class RefSimError(RuntimeError):
    """The program does something this reference does not model."""


def machine_constants(chip: Dict[str, Any],
                      timing: Dict[str, int]) -> Dict[str, Any]:
    """Constants of one machine: the configuration's ``chip`` block
    with a design point's timing fields put over it."""
    m = dict(chip)
    for k, v in timing.items():
        if k not in m:
            raise KeyError(f"timing field {k!r} is not a chip constant")
        m[k] = v
    m["tree_depth"] = int(math.log2(m["macro_rows"]
                                    // m["macro_element_rows"]))
    return m


def energy_breakdown(events: Dict[str, float],
                     table: Dict[str, float]) -> Dict[str, float]:
    out = {"compute": 0.0, "weight_load": 0.0, "noc": 0.0, "gmem": 0.0,
           "lmem": 0.0, "static": 0.0}
    for ev, count in events.items():
        cat, key = _ENERGY[ev]
        out[cat] += count * table[key]
    out["total"] = sum(out.values())
    return out


class _Core:
    __slots__ = ("cid", "prog", "pc", "time", "halted", "blocked", "g",
                 "s", "unit_free", "mgs")

    def __init__(self, cid: int, prog: Sequence[Tuple[str, Dict]]) -> None:
        self.cid = cid
        self.prog = prog
        self.pc = 0
        self.time = 0.0
        self.halted = False
        self.blocked = False
        self.g = [0] * 32
        self.s = {"ACC_DIV": 1}
        self.unit_free: Dict[str, float] = {}
        self.mgs: Dict[int, None] = {}


class _Stage:
    """One stage: all cores run to HALT; returns the makespan."""

    def __init__(self, m: Dict[str, Any], rnd: Callable[[float], float],
                 ev: Dict[str, float], busy: Dict[str, float]) -> None:
        self.m = m
        self.rnd = rnd
        self.ev = ev
        self.busy = busy
        self.instrs = 0
        self.links: Dict[Tuple[int, int], float] = {}
        self.ports = [0.0] * m["gmem_ports"]
        self.chan: Dict[Tuple[int, int, int], List] = {}
        self.link_bytes = m["flit_bytes"] * m["flits_per_cycle"]

    def add(self, key: str, amount: float) -> None:
        self.ev[key] = self.rnd(self.ev.get(key, 0.0) + amount)

    def use(self, c: _Core, unit: str, lat: float) -> float:
        rnd = self.rnd
        t = max(rnd(c.time + 1.0), c.unit_free.get(unit, 0.0))
        c.unit_free[unit] = rnd(t + lat)
        self.busy[unit] = rnd(self.busy.get(unit, 0.0) + lat)
        c.time = t
        return c.unit_free[unit]

    def issue_cycles(self, nbytes: int) -> float:
        return self.rnd(max(1.0, nbytes / self.link_bytes))

    def xy(self, cid: int) -> Tuple[int, int]:
        cols = self.m["mesh_cols"]
        return cid % cols, cid // cols

    def route(self, src: int, dst: int, nbytes: int, t: float) -> float:
        m, rnd = self.m, self.rnd
        flits = max(1, math.ceil(nbytes / m["flit_bytes"]))
        occupy = rnd(flits / m["flits_per_cycle"])
        t = rnd(t + m["inject_latency"])
        if src == dst:
            return rnd(t + occupy)
        (x, y), (dx, dy) = self.xy(src), self.xy(dst)
        cols = m["mesh_cols"]
        hops = 0
        while (x, y) != (dx, dy):
            if x != dx:
                nx, ny = x + (1 if dx > x else -1), y
            else:
                nx, ny = x, y + (1 if dy > y else -1)
            link = (y * cols + x, ny * cols + nx)
            t = rnd(max(t, self.links.get(link, 0.0))
                    + m["router_latency"])
            self.links[link] = rnd(t + occupy)
            x, y = nx, ny
            hops += 1
        self.add("noc_byte_hops", nbytes * hops)
        return rnd(t + occupy)

    def gmem(self, nbytes: int, t: float) -> float:
        i = min(range(len(self.ports)), key=lambda j: self.ports[j])
        t0 = max(t, self.ports[i])
        t1 = self.rnd(t0 + nbytes / self.m["gmem_port_bytes_per_cycle"])
        self.ports[i] = t1
        self.add("gmem_bytes", nbytes)
        return t1

    def run(self, programs: Sequence[Tuple[int, Sequence]]) -> float:
        cores = [_Core(cid, prog) for cid, prog in programs]
        by_id = {c.cid: c for c in cores}
        pending = [c for c in cores if c.prog]
        while True:
            nxt = None
            for c in pending:
                if not c.halted and not c.blocked and (
                        nxt is None or c.time < nxt.time):
                    nxt = c
            if nxt is None:
                if all(c.halted for c in pending):
                    break
                raise RefSimError("deadlock: every live core waits")
            self.step(nxt, by_id)
        return max((c.time for c in cores), default=0.0)

    def step(self, c: _Core, by_id: Dict[int, _Core]) -> None:
        if c.pc >= len(c.prog):
            c.halted = True
            return
        op, a = c.prog[c.pc]
        self.instrs += 1
        m, g, s = self.m, c.g, c.s
        if op == "HALT":
            c.pc += 1
            c.time = self.rnd(c.time + 1)
            c.halted = True
            return
        if op == "S_ADDI":
            self.use(c, "scalar", m["scalar_alu_latency"])
            if a["dst"]:
                g[a["dst"]] = g[a["a"]] + a["imm"]
        elif op == "S_LUI":
            self.use(c, "scalar", m["scalar_alu_latency"])
            if a["dst"]:
                g[a["dst"]] = (a["imm"] & 0xFFFF) << 16
        elif op == "CIM_CFG":
            self.use(c, "scalar", 1)
            s[a["sreg"]] = a["imm"]
        elif op == "CIM_CFGR":
            self.use(c, "scalar", 1)
            s[a["sreg"]] = g[a["src"]]
        elif op == "CIM_LOAD":
            rows, nlen = a["rows"], max(s.get("MG_NLEN", 0), 1)
            self.use(c, "cim", self.rnd(
                rows / m["weight_load_rows_per_cycle"]))
            self.add("cim_weight_load_bytes", rows * nlen)
            self.add("lmem_bytes", rows * nlen)
            c.mgs[a["mg"]] = None
        elif op == "CIM_MVM":
            rep = a["rep"]
            mask = ((s.get("MG_MASK_LO", 0) & 0xFFFF)
                    | (s.get("MG_MASK_HI", 0) << 16))
            active = sum(1 for i in c.mgs if mask & (1 << i))
            self.use(c, "cim", rep * m["act_bits"] + m["tree_depth"])
            self.add("cim_macro_passes",
                     rep * active * m["macros_per_group"])
            self.add("lmem_bytes", rep * (s.get("MVM_SEG_IN", 0)
                                          + s.get("MVM_SEG_OUT", 0)))
        elif op.startswith("V_") and op != "V_SETVL":
            fn = op[2:].lower()
            n = max(1, s.get("VLEN", 0)) * max(1, s.get("V_REP", 0))
            beats = -(-n // m["vector_lanes"])
            if fn in VEC_LUT:
                lat = beats * m["vector_special_latency"]
            elif fn in VEC_MUL:
                lat = beats + m["vector_mul_latency"]
            else:
                lat = beats + m["vector_alu_latency"]
            self.use(c, "vector", lat)
            self.add("vector_elems", n)
            self.add("lmem_bytes", n * (1 if a["i8"] else 4) * 2)
        elif op == "SEND":
            dst, size = g[a["core"]], g[a["size"]]
            done = self.use(c, "noc", self.issue_cycles(size))
            arrival = self.route(c.cid, dst, size, done)
            key = (c.cid, dst, s.get("CHANNEL", 0))
            self.chan.setdefault(key, []).append((arrival, size))
            self.add("lmem_bytes", size)
            peer = by_id.get(dst)
            if peer is not None:
                peer.blocked = False
        elif op == "RECV":
            src, size = g[a["core"]], g[a["size"]]
            q = self.chan.get((src, c.cid, s.get("CHANNEL", 0)))
            if not q:
                c.blocked = True
                return
            arrival, got = q.pop(0)
            if got != size:
                raise RefSimError(f"recv of {size} B met a {got} B message")
            c.time = max(c.time, arrival)
            self.use(c, "noc", self.issue_cycles(size))
            self.add("lmem_bytes", size)
        elif op in ("GLD", "GST"):
            size = g[a["size"]]
            done = self.gmem(size, self.rnd(c.time + 1))
            self.use(c, "noc", max(1.0, self.rnd(done - c.time - 1)))
            self.add("lmem_bytes", size)
        else:
            raise RefSimError(f"instruction {op} is not modelled")
        c.pc += 1


def simulate(stages: Sequence[Sequence[Tuple[int, Sequence]]],
             m: Dict[str, Any], energy_table: Dict[str, float],
             time_dtype: Any = np.float64) -> Dict[str, Any]:
    """Run every stage in order; the model's cycles are their sum."""
    if time_dtype is np.float64:
        rnd: Callable[[float], float] = float
    else:
        def rnd(v: float) -> float:
            return float(time_dtype(v))
    ev: Dict[str, float] = {}
    busy: Dict[str, float] = {}
    stage_cycles: List[float] = []
    instrs = 0
    for programs in stages:
        st = _Stage(m, rnd, ev, busy)
        stage_cycles.append(st.run(programs))
        instrs += st.instrs
    total = 0.0
    for c in stage_cycles:
        total = rnd(total + c)
    ev["static_core_cycles"] = rnd(total * m["n_cores"])
    return {"cycles": total, "stage_cycles": stage_cycles, "events": ev,
            "unit_busy": busy, "instrs": instrs,
            "energy": energy_breakdown(ev, energy_table)}
