"""Equivalence suite: jitted JAX stage engine vs the scalar interpreter.

The JAX engine (:mod:`repro.core.jaxsim`) must be *bit-identical* to
the scalar interpreter and the numpy vector engine — same cycles, same
stage makespans, same energy-event ledger, same per-unit busy totals,
same executed-instruction count.  This suite pins that contract on the
golden compiled workloads, hand-built corner cases and
hypothesis-randomized programs; it also pins the fleet contract (a
vmapped multi-machine decode equals a loop of single-machine runs over
the same compiled model), the ``ExplorationEngine(engine="jax")``
routing/caching behaviour, and the ``func:pallas`` oracle backend.
"""

import dataclasses
import itertools
import os

import numpy as np
import pytest

from repro import flow
from repro.core import jaxsim, vectorsim
from repro.core.arch import default_chip
from repro.core.codegen import StageProgram, _ensure_vec_flag_operand
from repro.core.isa import Program, SREG, default_isa
from repro.core.machine import machine_for
from repro.core.mapping import CostParams
from repro.core.simulator import ENGINES, Simulator
from repro.explore import (ExplorationEngine, FleetEvaluator,
                           canonical_chip, timing_space)
from repro.explore.records import EvalRecord

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.filterwarnings(
    "ignore:perf-mode lmem overflow:RuntimeWarning")

CHIP = default_chip()
ISA = default_isa()
_ensure_vec_flag_operand(ISA)


# ---------------------------------------------------------------------------
# helpers (mirrors test_vectorsim)
# ---------------------------------------------------------------------------


def run_stage_both(programs, chip=CHIP):
    sp = StageProgram(stage=None, schedules=[], programs=programs)
    out_s = Simulator(chip, ISA, engine="scalar")._run_stage(sp, None)
    out_j = jaxsim.run_stage(Simulator(chip, ISA, engine="jax"), sp)
    assert out_j is not None, "stage unexpectedly not decodable"
    return out_s, out_j


def assert_identical(out_s, out_j):
    makespan_s, events_s, busy_s, instrs_s = out_s
    makespan_j, events_j, busy_j, instrs_j = out_j
    assert makespan_j == makespan_s
    assert events_j == events_s
    assert busy_j == busy_s
    assert instrs_j == instrs_s


def assert_reports_identical(a, b):
    assert a.cycles == b.cycles
    assert a.stage_cycles == b.stage_cycles
    assert a.events == b.events
    assert a.unit_busy == b.unit_busy
    assert a.instrs == b.instrs


def prog(core_id, *instrs):
    p = Program(core_id=core_id)
    for op, args in instrs:
        p.append(ISA.instr(op, **args))
    return p


def I(op, **args):                       # noqa: E743 — terse test DSL
    return (op, args)


def _send(core, dst, size, stream, value_reg_base=1):
    r = value_reg_base
    return [
        I("CIM_CFG", sreg=SREG["CHANNEL"], imm=stream),
        I("S_ADDI", dst=r, a=0, imm=dst),
        I("S_ADDI", dst=r + 1, a=0, imm=64),
        I("S_ADDI", dst=r + 2, a=0, imm=size),
        I("SEND", core=r, src=r + 1, size=r + 2),
    ]


def _recv(core, src, size, stream, value_reg_base=4):
    r = value_reg_base
    return [
        I("CIM_CFG", sreg=SREG["CHANNEL"], imm=stream),
        I("S_ADDI", dst=r, a=0, imm=128),
        I("S_ADDI", dst=r + 1, a=0, imm=src),
        I("S_ADDI", dst=r + 2, a=0, imm=size),
        I("RECV", dst=r, core=r + 1, size=r + 2),
    ]


def _timing_chips(n=6):
    """Chips sharing CHIP's structure, varying only timing constants."""
    chips = []
    for i in range(n):
        chips.append(dataclasses.replace(
            CHIP,
            core=dataclasses.replace(
                CHIP.core,
                scalar=dataclasses.replace(CHIP.core.scalar,
                                           alu_latency=1 + i % 3,
                                           ldst_latency=2 + i % 2),
                vector=dataclasses.replace(CHIP.core.vector,
                                           alu_latency=1 + i % 4,
                                           mul_latency=2 + i % 3),
                cim=dataclasses.replace(
                    CHIP.core.cim,
                    weight_load_rows_per_cycle=1 + i % 4)),
            noc=dataclasses.replace(CHIP.noc,
                                    router_latency=1 + i % 3),
            clock_ghz=1.0 + 0.2 * i,
            name=f"t{i}"))
    return chips


# ---------------------------------------------------------------------------
# golden compiled workloads: jax == scalar
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,kw,strategy", [
    ("tiny_cnn", {}, "dp"),
    ("tiny_cnn", {}, "generic"),
    ("resnet18", {"res": 64}, "dp"),
    ("transformer", {"n_layers": 1, "d_model": 128, "n_heads": 4,
                     "seq": 16, "vocab": 64}, "dp"),
])
def test_golden_workload_equivalence(model, kw, strategy):
    art = flow.compile(model, CHIP,
                       flow.CompileOptions(strategy=strategy,
                                           params=CostParams(batch=2),
                                           workload_kw=kw or None))
    cm = art.ensure_model()
    scal = Simulator(CHIP, cm.isa, engine="scalar").run_model(cm)
    jx = Simulator(CHIP, cm.isa, engine="jax").run_model(cm)
    assert_reports_identical(scal, jx)


# ---------------------------------------------------------------------------
# hand-built corner cases
# ---------------------------------------------------------------------------


def test_recv_blocks_until_send():
    p0 = prog(0, *(_send(0, 1, 32, 7)
                   + [I("S_ADDI", dst=5, a=0, imm=1)] * 50
                   + [I("HALT", )]))
    p1 = prog(1, *(_recv(1, 0, 32, 7) + [I("HALT",)]))
    assert_identical(*run_stage_both({0: p0, 1: p1}))


def test_sync_barrier_and_gmem_ports():
    def core_prog(cid, delay):
        body = [I("S_ADDI", dst=1, a=0, imm=256),
                I("S_ADDI", dst=2, a=0, imm=1024 * cid),
                I("S_ADDI", dst=3, a=0, imm=200 + delay)]
        body += [I("NOP",)] * delay
        body += [I("GLD", dst=1, gaddr=2, size=3)]
        body += [I("SYNC", barrier=1)]
        body += [I("GST", src=1, gaddr=2, size=3)]
        body += [I("HALT",)]
        return prog(cid, *body)

    programs = {c: core_prog(c, 3 * c) for c in range(5)}
    assert_identical(*run_stage_both(programs))


def test_cfgr_and_lui_addi_chains():
    p = prog(0,
             I("S_LUI", dst=9, imm=2),
             I("S_ADDI", dst=9, a=9, imm=100),
             I("CIM_CFGR", sreg=SREG["VLEN"], src=9),
             I("V_ADD", dst=1, a=2, b=3),
             I("S_LD", dst=9, base=1, off=0),
             I("CIM_CFGR", sreg=SREG["VLEN"], src=9),
             I("V_ADD", dst=1, a=2, b=3),
             I("HALT",))
    assert_identical(*run_stage_both({0: p}))


def test_mvm_occupancy_and_vector_classes():
    p = prog(0,
             I("CIM_CFG", sreg=SREG["MG_NLEN"], imm=16),
             I("CIM_CFG", sreg=SREG["MG_KOFF"], imm=0),
             I("S_ADDI", dst=1, a=0, imm=0),
             I("CIM_LOAD", mg=0, src=1, rows=64),
             I("CIM_LOAD", mg=2, src=1, rows=32),
             I("CIM_CFG", sreg=SREG["MG_MASK_LO"], imm=0b101),
             I("CIM_CFG", sreg=SREG["MVM_SEG_IN"], imm=64),
             I("CIM_CFG", sreg=SREG["MVM_SEG_OUT"], imm=128),
             I("CIM_MVM", dst=1, src=1, rep=7, acc=0),
             I("V_SETVL", len=48),
             I("CIM_CFG", sreg=SREG["V_REP"], imm=3),
             I("V_MUL", dst=1, a=2, b=3),
             I("V_SIGMOID", dst=1, a=2, b=0),
             I("V_MAX", dst=1, a=2, b=3, flags=4),
             I("HALT",))
    assert_identical(*run_stage_both({0: p}))


def test_branchy_program_unrolls_statically():
    body = [I("S_ADDI", dst=1, a=0, imm=3),
            I("S_ADDI", dst=2, a=0, imm=0),
            I("S_ADDI", dst=1, a=1, imm=-1),
            I("BNE", a=1, b=2, off=-1),
            I("HALT",)]
    assert_identical(*run_stage_both({0: prog(0, *body)}))


def test_nonpow2_timing_constants_identical():
    """Non-dyadic latencies (1/3-cycle weight-load rows, 3-flit links)
    through the device latency mirrors: still bit-identical, because the
    host replays the device's per-instruction float64 latencies through
    the same summation order as the interpreter."""
    base = default_chip(n_cores=8, mesh_cols=4)
    chip = dataclasses.replace(
        base,
        core=dataclasses.replace(
            base.core,
            cim=dataclasses.replace(base.core.cim,
                                    weight_load_rows_per_cycle=3)),
        noc=dataclasses.replace(base.noc, flits_per_cycle=3),
        global_mem_bytes_per_cycle=48,
        name="nonpow2")
    art = flow.compile("tiny_cnn", chip,
                       flow.CompileOptions(params=CostParams(batch=2)))
    cm = art.ensure_model()
    scal = Simulator(chip, cm.isa, engine="scalar").run_model(cm)
    jx = Simulator(chip, cm.isa, engine="jax").run_model(cm)
    assert jx.cycles == scal.cycles
    assert jx.stage_cycles == scal.stage_cycles
    assert jx.events == scal.events
    assert jx.instrs == scal.instrs
    for unit, b in scal.unit_busy.items():
        assert jx.unit_busy[unit] == pytest.approx(b, rel=1e-12)


def test_engine_validation():
    assert "jax" in ENGINES
    with pytest.raises(ValueError):
        Simulator(CHIP, ISA, mode="func", engine="jax")
    with pytest.raises(ValueError):
        ExplorationEngine("tiny_cnn", engine="warp")


# ---------------------------------------------------------------------------
# fleet: vmapped multi-machine decode == loop of single runs
# ---------------------------------------------------------------------------


def test_canonical_chip_groups_timing_variants():
    chips = _timing_chips(4)
    canons = {canonical_chip(c) for c in chips}
    assert len(canons) == 1              # timing fields reset away
    assert canonical_chip(CHIP) in canons
    structural = dataclasses.replace(CHIP, n_cores=16, mesh_cols=4)
    assert canonical_chip(structural) != canonical_chip(CHIP)


def test_fleet_equals_loop_of_single_evals():
    """The satellite contract: one vmapped fleet evaluation is
    bit-identical (cycles, events-priced energy, throughput) to a loop
    of single-machine ``engine="jax"`` runs over the same compiled
    model."""
    chips = _timing_chips(6)
    cg = flow.compile("tiny_cnn", CHIP,
                      flow.CompileOptions(params=CostParams(batch=2))).cg
    fe = FleetEvaluator(cg, params=CostParams(batch=2))
    payloads = fe.evaluate([(c, "dp") for c in chips])
    art = flow.compile(cg, canonical_chip(chips[0]),
                       flow.CompileOptions(strategy="dp",
                                           params=CostParams(batch=2),
                                           fidelity="simulate"))
    cm = art.ensure_model()
    for chip, pl in zip(chips, payloads):
        rep = Simulator(chip, cm.isa, engine="jax").run_model(cm)
        assert pl["cycles"] == rep.cycles
        assert pl["energy"] == dict(rep.energy())
        # and the scalar interpreter agrees on the same pinned program
        scal = Simulator(chip, cm.isa, engine="scalar").run_model(cm)
        assert_reports_identical(scal, rep)


def test_fleet_stage_decoder_matches_per_machine():
    """FleetStageDecoder batches N machines through one vmapped call;
    per-machine outputs must equal independent single-machine decodes."""
    chips = _timing_chips(3)
    machines = [machine_for(c) for c in chips]
    programs = {c: prog(c, *(_send(c, (c + 1) % 3, 16, 10 + c)
                             + _recv(c, (c - 1) % 3, 16,
                                     10 + (c - 1) % 3)
                             + [I("V_SETVL", len=40),
                                I("V_ADD", dst=1, a=2, b=3),
                                I("HALT",)]))
                for c in range(3)}
    sp = StageProgram(stage=None, schedules=[], programs=programs)
    dec = jaxsim.FleetStageDecoder(ISA, machines)
    outs = dec.decode_stage(sp.programs)
    for chip, m, ds in zip(chips, machines, outs):
        sim = Simulator(chip, ISA, engine="jax")
        out_f = vectorsim.replay_stage(sim, sp, ds)
        out_1 = jaxsim.run_stage(sim, sp)
        assert_identical(out_1, out_f)
        out_s = Simulator(chip, ISA, engine="scalar")._run_stage(sp,
                                                                 None)
        assert_identical(out_s, out_f)


# a timing_space() grid on two structures: the default chip (tiny CNN)
# and a 16-core chip of single-macro groups that spreads a tiny looped
# LM over 16 stages with boundary items (as tests/test_arch_lowering.py)
_TINY_ARCH = dict(arch="ouro-2.6b", seq=8, layers=2, loop_steps=2,
                  d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                  d_ff=176)
_FLEET_MODELS = [
    pytest.param("tiny_cnn", {}, 2, {}, id="tiny_cnn"),
    pytest.param("arch", _TINY_ARCH, 1,
                 dict(n_cores=16, n_macro_groups=2, macros_per_group=1),
                 id="arch"),
]


def _grid_fleet(model, kw, batch, structure):
    """The points of a 2 x 2 x 2 timing_space() grid (scalar ALU x
    weight-load rate x router latency) and the program they share."""
    pts = [dataclasses.replace(p, **structure) for p in timing_space(
        scalar_alu=(1, 2), vector_alu=(2,), wl_rate=(1, 4),
        router=(1, 2)).points()]
    chips = [p.chip() for p in pts]
    cm = flow.compile(model, canonical_chip(chips[0]), flow.CompileOptions(
        strategy="dp", params=CostParams(batch=batch), fidelity="simulate",
        workload_kw=kw or None)).ensure_model()
    return pts, chips, cm


def _plan(ds):
    return (ds.items, ds.n_prog, ds.busy, ds.unit_used, ds.events,
            ds.ev_present, ds.n_static)


@pytest.mark.parametrize("model,kw,batch,structure", _FLEET_MODELS)
def test_fleet_shares_a_decoded_stage_only_across_router_latency(
        model, kw, batch, structure):
    """Router latency enters replay only, so machines that differ in it
    alone get one decoded stage object; machines whose scalar ALU or
    weight-load latencies differ get their own.  Every machine's plan
    equals its own single-machine decode's."""
    pts, chips, cm = _grid_fleet(model, kw, batch, structure)
    machines = [machine_for(c) for c in chips]
    dec = jaxsim.FleetStageDecoder(cm.isa, machines)
    for sp in cm.stages:
        outs = dec.decode_stage(sp.programs)
        for i, j in itertools.combinations(range(len(pts)), 2):
            router_only = (dataclasses.replace(pts[i], router_latency=0)
                           == dataclasses.replace(pts[j], router_latency=0))
            assert (outs[i] is outs[j]) == router_only, (i, j)
        for m, ds in zip(machines, outs):
            one = jaxsim.JaxStageDecoder(cm.isa, m).decode_stage(
                sp.programs)
            assert _plan(ds) == _plan(one)


@pytest.mark.parametrize("model,kw,batch,structure", _FLEET_MODELS)
def test_fleet_sharing_keeps_each_machines_statistics(
        model, kw, batch, structure):
    """With decoded stages shared, every machine's cycles, energy, unit
    busy and instruction count still equal its own
    ``Simulator(engine="jax")`` run."""
    pts, chips, cm = _grid_fleet(model, kw, batch, structure)
    fe = FleetEvaluator(cm.cg, params=CostParams(batch=batch))
    payloads = fe.evaluate([(c, "dp") for c in chips])
    dec = jaxsim.FleetStageDecoder(cm.isa, [machine_for(c)
                                            for c in chips])
    replays = [dec.decode_stage(sp.programs) for sp in cm.stages]
    for i, (chip, pl) in enumerate(zip(chips, payloads)):
        sim = Simulator(chip, cm.isa, engine="jax")
        rep = sim.run_model(cm)
        assert pl["cycles"] == rep.cycles
        assert pl["energy"] == dict(rep.energy())
        for sp, outs in zip(cm.stages, replays):
            assert_identical(jaxsim.run_stage(sim, sp),
                             vectorsim.replay_stage(sim, sp, outs[i]))


def _with_branch_penalty(chip, v):
    return dataclasses.replace(chip, core=dataclasses.replace(
        chip.core, scalar=dataclasses.replace(chip.core.scalar,
                                              branch_penalty=v)))


def _with_macros_per_group(chip, v):
    return dataclasses.replace(chip, core=dataclasses.replace(
        chip.core, cim=dataclasses.replace(chip.core.cim,
                                           macros_per_group=v)))


_VEC_AND_BRANCHY = {
    0: [I("V_SETVL", len=40), I("V_ADD", dst=1, a=2, b=3), I("HALT",)],
    1: [I("S_ADDI", dst=1, a=0, imm=3), I("S_ADDI", dst=2, a=0, imm=0),
        I("S_ADDI", dst=1, a=1, imm=-1), I("BNE", a=1, b=2, off=-1),
        I("HALT",)]}
_MVM = {0: [I("CIM_CFG", sreg=SREG["MG_NLEN"], imm=16),
            I("S_ADDI", dst=1, a=0, imm=0),
            I("CIM_LOAD", mg=0, src=1, rows=64),
            I("CIM_LOAD", mg=2, src=1, rows=32),
            I("CIM_CFG", sreg=SREG["MG_MASK_LO"], imm=0b101),
            I("CIM_MVM", dst=1, src=1, rep=7, acc=0), I("HALT",)]}


@pytest.mark.parametrize("change,bodies", [
    # enters only the decode-time unroll of a program with control flow
    pytest.param(_with_branch_penalty, _VEC_AND_BRANCHY, id="unroll"),
    # enters only the macro-pass ledger, never a latency
    pytest.param(_with_macros_per_group, _MVM, id="macros_per_group"),
])
def test_fleet_keys_every_machine_input_of_a_finish(change, bodies):
    """Two machines whose latency rows are equal but that differ in
    another input of their decoded stage keep one each, while a
    router-only pair still shares one."""
    base = CHIP
    chips = [base, dataclasses.replace(
        base, noc=dataclasses.replace(base.noc, router_latency=1)),
        change(base, 5)]
    programs = {c: prog(c, *body) for c, body in bodies.items()}
    machines = [machine_for(c) for c in chips]
    dec = jaxsim.FleetStageDecoder(ISA, machines)
    outs = dec.decode_stage(programs)
    assert outs[0] is outs[1] and outs[2] is not outs[0]
    for m, ds in zip(machines, outs):
        one = jaxsim.JaxStageDecoder(ISA, m).decode_stage(programs)
        assert _plan(ds) == _plan(one)
    # the batched core's items, built from the latency row alone, agree
    assert outs[0].items[0] == outs[2].items[0]


def test_device_call_downloads_integers_only():
    """Every array a stage-pass device call brings back is integer, on
    the single and the vmapped program: the float latencies are priced
    on the host, since a TPU's emulated float64 is not IEEE-exact."""
    cm = flow.compile("tiny_cnn", CHIP, flow.CompileOptions(
        params=CostParams(batch=2), fidelity="simulate")).ensure_model()
    machines = [machine_for(c) for c in _timing_chips(3)]
    fleet = jaxsim.FleetStageDecoder(cm.isa, machines)
    for sp in cm.stages:
        pr = fleet.prep(sp.programs)
        single_fn, fleet_fn = jaxsim._exec_for(fleet.npdecs[0],
                                               jaxsim._reg_bucket(pr))
        for fn, sc, want in (
                (single_fn, jaxsim._scalar_row(
                    machines[0].timing_constants()), (pr.n,)),
                (fleet_fn, fleet.tables.arrays, (3, pr.n))):
            lat, res = jaxsim._call_exec(fn, sc, jaxsim._device_cols(pr),
                                         pr.n)
            assert lat.shape == want
            got = {"lat": lat, **res}
            assert all(np.issubdtype(v.dtype, np.integer)
                       for v in got.values()), {k: v.dtype
                                                for k, v in got.items()}


# ---------------------------------------------------------------------------
# ExplorationEngine(engine="jax")
# ---------------------------------------------------------------------------


def test_explore_engine_jax_fleet(tmp_path):
    sp = timing_space(scalar_alu=(1,), vector_alu=(1, 3), wl_rate=(1, 4),
                      router=(2,))
    pts = list(sp.points())
    assert len(pts) == 4
    eng = ExplorationEngine("tiny_cnn", params=CostParams(batch=2),
                            cache=str(tmp_path / "jx"), engine="jax")
    recs = eng.evaluate(pts, fidelity="simulate")
    assert all(r.ok for r in recs)
    assert all(r.engine == "jax" for r in recs)
    # the all-defaults point shares its compile with per-point paths:
    # it must match the scalar engine bit-exactly
    default_pt = next(p for p in pts
                      if (p.scalar_alu_latency, p.vector_alu_latency,
                          p.weight_load_rows_per_cycle,
                          p.router_latency) == (1, 1, 1, 2))
    sc = ExplorationEngine("tiny_cnn", params=CostParams(batch=2),
                           cache=str(tmp_path / "sc"), engine="scalar")
    srec = sc.evaluate([default_pt], fidelity="simulate")[0]
    jrec = next(r for r in recs if r.point == default_pt)
    assert jrec.cycles == srec.cycles
    assert jrec.energy == srec.energy
    # second sweep: pure cache hits, identical payloads
    recs2 = eng.evaluate(pts, fidelity="simulate")
    assert all(r.cache_hit for r in recs2)
    assert [r.cycles for r in recs2] == [r.cycles for r in recs]
    # records round-trip the engine field
    rt = EvalRecord.from_dict(recs[0].to_dict())
    assert rt.engine == "jax"
    assert rt.row()["engine"] == "jax"


def test_jax_cache_key_is_marked(tmp_path):
    """Pinned-program (fleet) simulate results must never share cache
    entries with per-point-compiled results; cheap fidelities (no
    simulator run) keep one shared key."""
    pt = next(iter(timing_space(scalar_alu=(2,), vector_alu=(1,),
                                wl_rate=(1,), router=(2,)).points()))
    jx = ExplorationEngine("tiny_cnn", engine="jax")
    sc = ExplorationEngine("tiny_cnn", engine="scalar")
    au = ExplorationEngine("tiny_cnn")
    assert jx._key(pt, "simulate") != sc._key(pt, "simulate")
    assert sc._key(pt, "simulate") == au._key(pt, "simulate")
    assert jx._key(pt, "analytic") == au._key(pt, "analytic")


def test_timing_point_chip_roundtrip():
    """Timing-only DesignPoint fields land on the chip; the all-default
    point builds the identical historical chip object (cache keys on
    chip().to_dict() stay stable)."""
    pts = list(timing_space(scalar_alu=(1, 2), vector_alu=(1,),
                            wl_rate=(4,), router=(1,)).points())
    for p in pts:
        c = p.chip()
        assert c.core.scalar.alu_latency == p.scalar_alu_latency
        assert c.core.cim.weight_load_rows_per_cycle == 4
        assert c.noc.router_latency == 1
    from repro.explore.space import DesignPoint
    a = DesignPoint().chip().to_dict()
    b = default_chip().to_dict()
    a.pop("name"), b.pop("name")         # labels are cosmetic
    assert a == b


# ---------------------------------------------------------------------------
# func:pallas oracle backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,kw", [
    ("tiny_cnn", {"res": 8}),
    ("transformer", dict(n_layers=1, d_model=128, n_heads=4, seq=16,
                         vocab=64)),
])
def test_func_pallas_bit_exact(model, kw):
    """The Pallas bit-serial oracle must agree bit-exactly with the
    numpy oracle (check=True raises on any group mismatch)."""
    art = flow.compile(model, CHIP, flow.CompileOptions(
        strategy="dp", batch=2, workload_kw=kw, fidelity="analytic"))
    rep = art.evaluate("func:pallas")
    assert rep.backend == "func:pallas"
    assert rep.outputs and all(a.dtype == np.int8
                               for a in rep.outputs.values())
    assert rep.cycles == 0.0             # no timing claim


def test_func_pallas_rejects_partial_tensors():
    art = flow.compile("tiny_cnn", CHIP, flow.CompileOptions(
        strategy="dp", batch=1, workload_kw={"res": 8},
        fidelity="analytic"))
    with pytest.raises(TypeError):
        art.evaluate("func:pallas", inputs=np.zeros((1, 8, 8, 3),
                                                    dtype=np.int8))


def test_auto_interpret_follows_backend_memoized(monkeypatch):
    """Interpret mode is picked from the backend alone, once: native on
    a TPU, the interpreter everywhere else."""
    import jax
    from repro.kernels import ops
    calls = []

    def fake_backend():
        calls.append(1)
        return "tpu"

    ops._auto_interpret.cache_clear()
    monkeypatch.setattr(jax, "default_backend", fake_backend)
    try:
        assert ops._auto_interpret() is False
        assert ops._auto_interpret() is False
        assert len(calls) == 1                          # memoized
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        ops._auto_interpret.cache_clear()
        assert ops._auto_interpret() is True
    finally:
        ops._auto_interpret.cache_clear()


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The persistent compile cache honours $JAX_COMPILATION_CACHE_DIR
    (set nothing: JAX reads it itself) and otherwise sits at the fixed
    ``<checkout>/results/jax_cache``."""
    import jax
    from repro import compile_cache
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(checkout, "results", "jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.enable_compile_cache() == want  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# hypothesis: randomized decodable programs, jax == scalar
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    _N_CORES = 3

    @st.composite
    def stage_programs(draw):
        """Random multi-core stage in the decodable subset (the
        construction from test_vectorsim: sends before recvs within a
        phase, unique streams, all-core SYNC between phases)."""
        rng_local = st.sampled_from([
            lambda d: [I("NOP",)],
            lambda d: [I("S_ADDI", dst=d.draw(st.integers(1, 5)), a=0,
                         imm=d.draw(st.integers(-100, 100)))],
            lambda d: [I("S_LUI", dst=d.draw(st.integers(1, 5)),
                         imm=d.draw(st.integers(0, 50)))],
            lambda d: [I("S_LD", dst=6, base=1, off=0)],
            lambda d: [I("V_SETVL", len=d.draw(st.integers(1, 200)))],
            lambda d: [I("CIM_CFG", sreg=SREG["V_REP"],
                         imm=d.draw(st.integers(0, 4)))],
            lambda d: [I("V_ADD", dst=1, a=2, b=3)],
            lambda d: [I("V_QUANT", dst=1, a=2, b=0,
                         flags=d.draw(st.sampled_from([0, 4])))],
            lambda d: [I("V_EXP", dst=1, a=2, b=0)],
            lambda d: [I("CIM_CFG", sreg=SREG["MG_NLEN"],
                         imm=d.draw(st.integers(1, 64)))],
            lambda d: [I("CIM_LOAD", mg=d.draw(st.integers(0, 3)),
                         src=1, rows=d.draw(st.integers(1, 128)))],
            lambda d: [I("CIM_CFG", sreg=SREG["MG_MASK_LO"],
                         imm=d.draw(st.integers(0, 15)))],
            lambda d: [I("CIM_MVM", dst=1, src=2,
                         rep=d.draw(st.integers(1, 8)),
                         acc=d.draw(st.sampled_from([0, 1])))],
            lambda d: [I("S_ADDI", dst=7, a=0,
                         imm=d.draw(st.integers(1, 300))),
                       I("GLD", dst=1, gaddr=2, size=7)],
            lambda d: [I("S_ADDI", dst=8, a=0,
                         imm=d.draw(st.integers(1, 64))),
                       I("BCAST", src=1, size=8)],
        ])

        # a class body cannot read the enclosing ``draw`` while
        # binding a name of its own to it, so build the namespace here
        _D = type("_D", (), {"draw": staticmethod(draw)})

        n_phases = draw(st.integers(1, 2))
        chunks = {c: [] for c in range(_N_CORES)}
        stream = 0
        for phase in range(n_phases):
            sends = {c: [] for c in chunks}
            recvs = {c: [] for c in chunks}
            for _ in range(draw(st.integers(0, 3))):
                src = draw(st.integers(0, _N_CORES - 1))
                dst = draw(st.integers(0, _N_CORES - 1))
                if src == dst:
                    continue
                size = draw(st.integers(1, 96))
                sends[src].extend(_send(src, dst, size, stream))
                recvs[dst].extend(_recv(dst, src, size, stream))
                stream += 1
            for c in chunks:
                ops = []
                for _ in range(draw(st.integers(0, 6))):
                    ops.extend(draw(rng_local)(_D))
                chunks[c].extend(sends[c] + ops + recvs[c])
                chunks[c].append(I("SYNC", barrier=phase))
        programs = {}
        for c, body in chunks.items():
            if draw(st.booleans()):
                body.append(I("HALT",))
            programs[c] = prog(c, *body)
        return programs

    @settings(max_examples=25, deadline=None)
    @given(stage_programs())
    def test_random_programs_identical(programs):
        assert_identical(*run_stage_both(programs))

    @settings(max_examples=10, deadline=None)
    @given(stage_programs())
    def test_random_programs_fleet_identical(programs):
        """vmapped fleet decode == per-machine scalar interpreter on
        randomized programs across timing-diverse machines.  With a
        non-dyadic weight-load rate (3 rows per cycle) the interpreter
        sums its busy ledger in another order, so there busy agrees
        within an ulp and the rest stays exact, as in
        ``test_nonpow2_timing_constants_identical``; the numpy engine
        is matched exactly on every machine."""
        chips = _timing_chips(3)
        sp = StageProgram(stage=None, schedules=[], programs=programs)
        dec = jaxsim.FleetStageDecoder(
            ISA, [machine_for(c) for c in chips])
        outs = dec.decode_stage(sp.programs)
        for chip, ds in zip(chips, outs):
            out_f = vectorsim.replay_stage(
                Simulator(chip, ISA, engine="jax"), sp, ds)
            out_s = Simulator(chip, ISA,
                              engine="scalar")._run_stage(sp, None)
            rate = chip.core.cim.weight_load_rows_per_cycle
            if rate & (rate - 1) == 0:
                assert_identical(out_s, out_f)
                continue
            out_v = vectorsim.run_stage(
                Simulator(chip, ISA, engine="vector"), sp)
            assert_identical(out_v, out_f)
            assert out_f[0] == out_s[0]
            assert out_f[1] == out_s[1]
            assert out_f[3] == out_s[3]
            assert out_f[2].keys() == out_s[2].keys()
            for unit, b in out_s[2].items():
                assert out_f[2][unit] == pytest.approx(b, rel=1e-12)
