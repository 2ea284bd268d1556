"""Equivalence suite: vectorized pre-decoded engine vs scalar interpreter.

The perf-mode simulator's vectorized engine (:mod:`repro.core.vectorsim`)
must be *bit-identical* to the scalar interpreter — same cycles, same
stage makespans, same energy-event ledger, same per-unit busy totals,
same executed-instruction count (including blocked-RECV retries).  This
suite pins that contract on the golden compiled workloads and on
hypothesis-randomized programs covering the decodable subset
(communication rendezvous, barriers, gmem port contention, blocked
receives), plus the fallback semantics for programs outside it.
"""

import numpy as np
import pytest

from repro import flow
from repro.core import vectorsim
from repro.core.arch import default_chip
from repro.core.codegen import StageProgram, _ensure_vec_flag_operand
from repro.core.isa import Instr, Program, SREG, default_isa
from repro.core.mapping import CostParams
from repro.core.simulator import Deadlock, SimError, Simulator

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
# helpers
# ---------------------------------------------------------------------------


def run_stage_both(programs):
    """(makespan, events, busy, instrs) from both engines on one stage."""
    sp = StageProgram(stage=None, schedules=[], programs=programs)
    scal = Simulator(CHIP, ISA, engine="scalar")
    out_s = scal._run_stage(sp, None)
    vec = Simulator(CHIP, ISA, engine="vector")
    out_v = vectorsim.run_stage(vec, sp)
    assert out_v is not None, "stage unexpectedly not decodable"
    return out_s, out_v


def assert_identical(out_s, out_v):
    makespan_s, events_s, busy_s, instrs_s = out_s
    makespan_v, events_v, busy_v, instrs_v = out_v
    assert makespan_v == makespan_s
    assert events_v == events_s
    assert busy_v == busy_s
    assert instrs_v == instrs_s


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


# ---------------------------------------------------------------------------
# golden compiled workloads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,kw,strategy", [
    ("tiny_cnn", {}, "dp"),
    ("tiny_cnn", {}, "generic"),
    ("resnet18", {"res": 64}, "dp"),
    # dynamic-weight attention: per-sample mid-stage CIM writes must
    # replay bit-identically (weight gather V_MOVs + CIM_LOAD from the
    # RECV'd activations are core-local block ops)
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
    vec = Simulator(CHIP, cm.isa, engine="vector").run_model(cm)
    assert_reports_identical(scal, vec)


def test_golden_vector_engine_actually_used():
    """engine='vector' must not silently fall back on compiled code."""
    art = flow.compile("tiny_cnn", CHIP,
                       flow.CompileOptions(params=CostParams(batch=2)))
    cm = art.ensure_model()
    rep = Simulator(CHIP, cm.isa, engine="vector").run_model(cm)
    assert rep.cycles > 0


# ---------------------------------------------------------------------------
# hand-built corner cases
# ---------------------------------------------------------------------------


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


def test_recv_blocks_until_send():
    # receiver is scheduled first, blocks, retries — retry attempts
    # count as executed instructions in both engines
    p0 = prog(0, *(_send(0, 1, 32, 7)
                   + [I("S_ADDI", dst=5, a=0, imm=1)] * 50
                   + [I("HALT", )]))
    p1 = prog(1, *(_recv(1, 0, 32, 7) + [I("HALT",)]))
    assert_identical(*run_stage_both({0: p0, 1: p1}))


def test_recv_size_mismatch_raises_same():
    p0 = prog(0, *(_send(0, 1, 32, 3) + [I("HALT",)]))
    p1 = prog(1, *(_recv(1, 0, 16, 3) + [I("HALT",)]))
    sp = StageProgram(stage=None, schedules=[], programs={0: p0, 1: p1})
    with pytest.raises(SimError, match="size mismatch"):
        Simulator(CHIP, ISA, engine="scalar")._run_stage(sp, None)
    with pytest.raises(SimError, match="size mismatch"):
        vectorsim.run_stage(Simulator(CHIP, ISA, engine="vector"), sp)


def test_deadlock_raises_same():
    p0 = prog(0, *(_recv(0, 1, 8, 1) + [I("HALT",)]))
    p1 = prog(1, I("HALT",))
    sp = StageProgram(stage=None, schedules=[], programs={0: p0, 1: p1})
    with pytest.raises(Deadlock):
        Simulator(CHIP, ISA, engine="scalar")._run_stage(sp, None)
    with pytest.raises(Deadlock):
        vectorsim.run_stage(Simulator(CHIP, ISA, engine="vector"), sp)


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
    # big S_Reg value through the G_Reg path (CIM_CFGR), LUI/ADDI pairs
    p = prog(0,
             I("S_LUI", dst=9, imm=2),              # 0x20000
             I("S_ADDI", dst=9, a=9, imm=100),
             I("CIM_CFGR", sreg=SREG["VLEN"], src=9),
             I("V_ADD", dst=1, a=2, b=3),           # vlen = 131172
             I("S_LD", dst=9, base=1, off=0),       # perf: no writeback
             I("CIM_CFGR", sreg=SREG["VLEN"], src=9),
             I("V_ADD", dst=1, a=2, b=3),           # vlen unchanged
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
             I("V_MUL", dst=1, a=2, b=3),            # mul class
             I("V_SIGMOID", dst=1, a=2, b=0),        # special class
             I("V_MAX", dst=1, a=2, b=3, flags=4),   # alu class, i8
             I("HALT",))
    assert_identical(*run_stage_both({0: p}))


def test_dead_code_after_halt_is_ignored():
    # unsupported ops after HALT must not force the scalar fallback —
    # the interpreter never dispatches them either
    p = prog(0,
             I("S_ADDI", dst=1, a=0, imm=3),
             I("HALT",),
             I("S_ADD", dst=1, a=1, b=1),    # dead, outside the subset
             I("BEQ", a=0, b=0, off=-2))     # dead branch
    sp = StageProgram(stage=None, schedules=[], programs={0: p})
    out_v = vectorsim.run_stage(Simulator(CHIP, ISA, engine="vector"),
                                sp)
    assert out_v is not None
    out_s = Simulator(CHIP, ISA, engine="scalar")._run_stage(sp, None)
    assert_identical(out_s, out_v)


def test_branchy_program_unrolls_statically():
    # a live countdown loop is statically resolved at decode time (the
    # perf-mode register file never depends on simulated data): the
    # vector engine unrolls it and stays bit-identical, including the
    # per-iteration branch latencies and instruction counts
    body = [I("S_ADDI", dst=1, a=0, imm=3),
            I("S_ADDI", dst=2, a=0, imm=0),
            I("S_ADDI", dst=1, a=1, imm=-1),
            I("BNE", a=1, b=2, off=-1),
            I("HALT",)]
    p = prog(0, *body)
    assert_identical(*run_stage_both({0: p}))


def test_scalar_alu_chain_unrolls():
    # cross-register scalar ALU chains feeding a GLD size / a vector
    # length: resolved by the decode-time pre-execution
    body = [I("S_ADDI", dst=1, a=0, imm=6),
            I("S_ADDI", dst=2, a=0, imm=7),
            I("S_MUL", dst=3, a=1, b=2),        # 42
            I("S_ADD", dst=3, a=3, b=1),        # 48
            I("S_ADDI", dst=4, a=0, imm=256),
            I("GLD", dst=4, gaddr=4, size=3),
            I("CIM_CFGR", sreg=SREG["VLEN"], src=3),
            I("V_ADD", dst=1, a=2, b=3),
            I("HALT",)]
    p = prog(0, *body)
    assert_identical(*run_stage_both({0: p}))


def test_loop_with_comms_unrolls():
    # a loop body containing SEND/RECV rendezvous: the unrolled trace
    # must preserve boundary ordering and per-retry instruction counts
    sends = []
    recvs = []
    for it in range(3):
        sends += _send(0, 1, 16, 40 + it)
        recvs += _recv(1, 0, 16, 40 + it)
    p0 = prog(0, *(sends
                   + [I("S_ADDI", dst=9, a=0, imm=2),
                      I("S_ADDI", dst=9, a=9, imm=-1),
                      I("BNE", a=9, b=0, off=-1),
                      I("HALT",)]))
    p1 = prog(1, *(recvs + [I("HALT",)]))
    assert_identical(*run_stage_both({0: p0, 1: p1}))


def test_custom_op_falls_back_to_scalar():
    # instructions outside even the unrollable subset (custom
    # descriptors the simulator has no semantics for) still force the
    # per-stage fallback; engine="vector" must refuse
    from repro.core.isa import InstrDescriptor, default_isa as _disa
    isa2 = _disa()
    isa2.register(InstrDescriptor(name="X_CUSTOM", opcode=60, fmt="J",
                                  unit="scalar", operands={}))
    p = Program(core_id=0)
    p.append(isa2.instr("X_CUSTOM"))
    p.append(isa2.instr("HALT"))
    sp = StageProgram(stage=None, schedules=[], programs={0: p})
    assert vectorsim.run_stage(Simulator(CHIP, isa2, engine="vector"),
                               sp) is None

    class _M:                     # minimal CompiledModel stand-in
        stages = [sp]
        layout = None

    with pytest.raises(SimError, match="not statically decodable"):
        Simulator(CHIP, isa2, engine="vector").run_model(_M())


def test_auto_engine_fallback_equivalence(monkeypatch):
    # engine="auto" must fall back per stage and report identically to
    # the interpreter.  A tiny unroll cap forces the branchy program
    # out of the decodable subset without needing an op the scalar
    # interpreter cannot execute.
    monkeypatch.setattr(vectorsim.StageDecoder, "UNROLL_CAP", 4)
    body = [I("S_ADDI", dst=1, a=0, imm=5),
            I("S_ADDI", dst=2, a=0, imm=0),
            I("S_ADDI", dst=1, a=1, imm=-1),
            I("BNE", a=1, b=2, off=-1),
            I("HALT",)]
    p = prog(0, *body)
    sp = StageProgram(stage=None, schedules=[], programs={0: p})
    assert vectorsim.run_stage(Simulator(CHIP, ISA, engine="vector"),
                               sp) is None

    class _M:
        stages = [sp]
        layout = None

    rep_auto = Simulator(CHIP, ISA, engine="auto").run_model(_M())
    rep_scal = Simulator(CHIP, ISA, engine="scalar").run_model(_M())
    assert_reports_identical(rep_scal, rep_auto)


def test_engine_validation():
    with pytest.raises(ValueError):
        Simulator(CHIP, ISA, engine="warp")
    with pytest.raises(ValueError):
        Simulator(CHIP, ISA, mode="func", engine="vector")


def test_nonpow2_bandwidth_divisors_exact():
    """Block replay pre-sums run latencies — exact for dyadic latencies
    by construction.  A chip with non-power-of-two bandwidth divisors
    (1/3-cycle weight-load rows, 3-flit links, 48 B/cycle gmem ports)
    produces non-dyadic floats where re-association *could* differ in
    the last ulp; pin that the replay still matches the interpreter
    bit-exactly on a compiled workload (the run-collapse only ever adds
    the same addends in the same left-to-right order)."""
    import dataclasses
    base = default_chip(n_cores=8, mesh_cols=4)
    chip = dataclasses.replace(
        base,
        core=dataclasses.replace(
            base.core,
            cim=dataclasses.replace(base.core.cim,
                                    weight_load_rows_per_cycle=3)),
        noc=dataclasses.replace(base.noc, flits_per_cycle=3),
        global_mem_bytes_per_cycle=48,
        name="nonpow2-divisors")
    art = flow.compile("tiny_cnn", chip,
                       flow.CompileOptions(params=CostParams(batch=2)))
    cm = art.ensure_model()
    scal = Simulator(chip, cm.isa, engine="scalar").run_model(cm)
    vec = Simulator(chip, cm.isa, engine="vector").run_model(cm)
    # timing is bit-exact even for non-dyadic latencies: the replay's
    # run collapse adds the same addends in the interpreter's order
    assert vec.cycles == scal.cycles
    assert vec.stage_cycles == scal.stage_cycles
    assert vec.events == scal.events
    assert vec.instrs == scal.instrs
    # the busy *ledger* is a pure sum and may re-associate: bound it at
    # one ulp (documented exactness note from the PR-4 ROADMAP entry)
    for unit, b in scal.unit_busy.items():
        assert vec.unit_busy[unit] == pytest.approx(b, rel=1e-12)


def test_lazy_lmem_allocation():
    from repro.core.simulator import _Core
    perf = _Core(0, Program(core_id=0), CHIP, func=False)
    assert perf.lmem is None and perf._lmem is None
    func = _Core(0, Program(core_id=0), CHIP, func=True)
    assert func._lmem is None            # nothing allocated up front
    assert func.lmem is not None         # materializes on first touch
    assert func.lmem.nbytes == CHIP.core.local_mem.size_bytes


def test_packed_program_columns():
    p = prog(3, I("S_ADDI", dst=4, a=0, imm=-7),
             I("CIM_CFG", sreg=5, imm=9), I("HALT",))
    packed = p.pack(ISA)
    assert len(packed) == 3
    assert packed.core_id == 3
    assert packed.op.tolist() == [ISA.op_id("S_ADDI"),
                                  ISA.op_id("CIM_CFG"),
                                  ISA.op_id("HALT")]
    assert packed.col("imm").tolist() == [-7, 9, 0]
    assert packed.col("dst").tolist() == [4, 0, 0]
    assert p.pack(ISA) is packed         # memoized


# ---------------------------------------------------------------------------
# hypothesis: randomized decodable programs
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    _N_CORES = 3

    @st.composite
    def stage_programs(draw):
        """Random multi-core stage in the decodable subset.

        Construction guarantees liveness: within a phase every core
        emits its SENDs before its RECVs (SEND never blocks), message
        streams are unique per message (per-channel FIFO is trivially
        consistent), and phases end with an all-core SYNC.
        """
        rng_local = st.sampled_from([
            lambda d: [I("NOP",)],
            lambda d: [I("S_ADDI", dst=d.draw(st.integers(1, 5)), a=0,
                         imm=d.draw(st.integers(-100, 100)))],
            lambda d: [I("S_LUI", dst=d.draw(st.integers(1, 5)),
                         imm=d.draw(st.integers(0, 50)))],
            lambda d: [I("S_LD", dst=6, base=1, off=0)],
            lambda d: [I("S_ST", src=6, base=1, off=4)],
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
            lambda d: [I("S_ADDI", dst=7, a=0,
                         imm=d.draw(st.integers(1, 300))),
                       I("GST", src=1, gaddr=2, size=7)],
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
                # sends first (never block), then local work, then recvs
                chunks[c].extend(sends[c] + ops + recvs[c])
                chunks[c].append(I("SYNC", barrier=phase))
        programs = {}
        for c, body in chunks.items():
            if draw(st.booleans()):
                body.append(I("HALT",))   # else: END-of-program path
            programs[c] = prog(c, *body)
        return programs

    @settings(max_examples=30, deadline=None)
    @given(stage_programs())
    def test_random_programs_identical(programs):
        assert_identical(*run_stage_both(programs))
