"""Evaluation backends for :class:`repro.flow.Artifact`.

A :class:`Backend` turns a compiled artifact into an
:class:`EvalReport` — the one result shape shared by every fidelity:

* :class:`AnalyticBackend` — the mapping cost model's stage latencies
  and energy-event ledger (no codegen; fast screening fidelity).
* :class:`TraceBackend` — replays each StagePlan at unit/transfer
  granularity on :class:`repro.core.trace.TraceEngine` (no codegen, no
  per-instruction stepping; the middle rung of the fidelity ladder).
* :class:`SimulatorBackend` — runs the per-core ISA streams on the
  cycle-accurate simulator (``mode="perf"``) or the functional ISS
  (``mode="func"``, which additionally needs a ``gmem_image``).

All three price energy through the shared
:class:`~repro.core.machine.MachineModel`; the analytic and trace
backends additionally honor ``CompileOptions.calibration`` (fit via
:func:`repro.flow.calibrate`).

Backends resolve by name through :data:`BACKENDS` (``"analytic"``,
``"trace"``, ``"simulate"``/``"perf"``, ``"func"``), so
``artifact.evaluate(backend="simulate")`` and custom registered
backends compose without touching callers.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Union

import numpy as np

from ..core.machine import machine_for
from ..core.simulator import ENGINES, SimReport, Simulator
from ..core.trace import TraceEngine, TraceReport
from ..obs import span

__all__ = ["EvalReport", "Backend", "AnalyticBackend", "TraceBackend",
           "SimulatorBackend", "PallasFuncBackend", "BACKENDS",
           "resolve_backend", "register_backend",
           "backend_for_fidelity"]


@dataclass
class EvalReport:
    """One artifact evaluation, identical shape across fidelities."""

    backend: str                   # resolved backend name
    cycles: float
    energy: Dict[str, float]       # nJ breakdown, incl. "total"
    throughput_sps: float          # samples/s at the chip clock
    batch: int
    wall_s: float = 0.0
    sim: Optional[SimReport] = None     # simulator backends only
    trace: Optional[TraceReport] = None  # trace backend only
    outputs: Optional[Dict[int, np.ndarray]] = None  # func oracles only

    @property
    def energy_total(self) -> float:
        return self.energy.get("total", 0.0)

    @property
    def edp(self) -> float:
        return self.cycles * self.energy_total

    def summary(self) -> str:
        return (f"[{self.backend}] {self.cycles:.0f} cycles, "
                f"{self.energy_total / 1e6:.3f} mJ, "
                f"{self.throughput_sps:.1f} samples/s "
                f"(batch={self.batch})")


def _throughput(chip: Any, cycles: float, batch: int) -> float:
    if cycles <= 0:
        return 0.0
    return batch / (cycles / (chip.clock_ghz * 1e9))


class Backend:
    """Evaluation backend protocol: ``evaluate(artifact) -> EvalReport``."""

    name: str = "backend"
    requires_model: bool = False

    def evaluate(self, artifact: Any, **kw: Any) -> EvalReport:
        raise NotImplementedError


class AnalyticBackend(Backend):
    """The mapping cost model — no ISA, no simulator."""

    name = "analytic"
    requires_model = False

    def evaluate(self, artifact: Any, **kw: Any) -> EvalReport:
        if kw:
            raise TypeError(f"analytic backend takes no extra "
                            f"arguments, got {sorted(kw)}")
        t0 = time.perf_counter()
        res = artifact.partition
        batch = artifact.options.resolved_batch()
        calib = artifact.options.calibration
        cycles = float(res.latency_cycles(batch, calib))
        energy = dict(machine_for(artifact.chip).price_events(
            res.energy_events(batch, calib)))
        return EvalReport(
            backend=self.name, cycles=cycles, energy=energy,
            throughput_sps=_throughput(artifact.chip, cycles, batch),
            batch=batch, wall_s=time.perf_counter() - t0)


class TraceBackend(Backend):
    """StagePlan replay on the shared machine model (middle fidelity)."""

    name = "trace"
    requires_model = False

    def evaluate(self, artifact: Any, **kw: Any) -> EvalReport:
        if kw:
            raise TypeError(f"trace backend takes no extra arguments, "
                            f"got {sorted(kw)}")
        t0 = time.perf_counter()
        batch = artifact.options.resolved_batch()
        engine = TraceEngine(artifact.chip,
                             artifact.options.calibration)
        rep = engine.run(artifact.partition, batch)
        return EvalReport(
            backend=self.name, cycles=float(rep.cycles),
            energy=dict(rep.energy()),
            throughput_sps=_throughput(artifact.chip, rep.cycles, batch),
            batch=batch, wall_s=time.perf_counter() - t0, trace=rep)


class SimulatorBackend(Backend):
    """Cycle-accurate (``perf``) / functional ISS (``func``) execution.

    ``engine`` selects the perf-mode execution path: ``"auto"``
    (default) replays pre-decoded basic blocks on the vectorized engine
    and falls back to the scalar interpreter for programs outside its
    static subset; ``"scalar"`` forces the interpreter, ``"vector"``
    forbids the fallback.  Both paths are cycle- and event-identical
    (pinned by ``tests/test_vectorsim.py``); an ``engine=...`` keyword
    on ``evaluate`` overrides per call.
    """

    requires_model = True

    def __init__(self, mode: str = "perf", name: Optional[str] = None,
                 engine: str = "auto") -> None:
        if mode not in ("perf", "func"):
            raise ValueError(f"mode must be 'perf' or 'func', "
                             f"got {mode!r}")
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, "
                             f"got {engine!r}")
        self.mode = mode
        self.engine = engine
        self.name = name or ("simulate" if mode == "perf" else "func")

    def evaluate(self, artifact: Any,
                 gmem_image: Optional[np.ndarray] = None,
                 engine: Optional[str] = None,
                 faults: Optional[Any] = None,
                 **kw: Any) -> EvalReport:
        if kw:
            raise TypeError(f"simulator backend takes only gmem_image, "
                            f"engine and faults, got {sorted(kw)}")
        t0 = time.perf_counter()
        model = artifact.ensure_model()
        # pass the engine through unchanged: Simulator itself rejects
        # func+vector and unknown engines, so an explicit override is
        # never silently ignored.  ``faults`` (functional mode) is a
        # repro.faults.PhysicalCimFaults injecting stuck bits at
        # CIM_LOAD time.
        sim = Simulator(artifact.chip, model.isa, mode=self.mode,
                        engine=engine or self.engine, faults=faults)
        rep = sim.run_model(model, gmem_image=gmem_image)
        batch = model.batch
        return EvalReport(
            backend=self.name, cycles=float(rep.cycles),
            energy=dict(rep.energy()),
            throughput_sps=_throughput(artifact.chip, rep.cycles, batch),
            batch=batch, wall_s=time.perf_counter() - t0, sim=rep)


class PallasFuncBackend(Backend):
    """Functional oracle with the MVMs on the Pallas bit-serial kernel.

    Forward-passes the artifact's condensed graph through
    :func:`repro.core.ref.run_reference`, executing every INT8 matmul
    on :func:`repro.kernels.ops.cim_mvm` — the bit-serial bit-plane
    decomposition a digital CIM macro performs, as a Pallas kernel
    (interpret mode off a TPU, native on a TPU).  With ``check=True``
    (default) the pure-numpy oracle runs alongside and every group
    output is asserted bit-equal, so one evaluation validates the
    kernel's integer semantics at full-model scale — feasible where the
    per-instruction functional ISS is not (e.g. resnet18 at 224x224).

    ``weights``/``biases``/``inputs``/``quant`` default to
    :func:`repro.core.ref.random_init` + ``auto_quant`` draws, making
    ``artifact.evaluate("func:pallas")`` self-contained; ``norms`` (the
    RMSNorm gains by ``Op.param_owner``) default to ones.
    """

    name = "func:pallas"
    requires_model = False

    def evaluate(self, artifact: Any, weights: Any = None,
                 biases: Any = None, inputs: Any = None,
                 quant: Any = None, check: bool = True,
                 seed: int = 0, faults: Any = None, norms: Any = None,
                 **kw: Any) -> EvalReport:
        if kw:
            raise TypeError(f"func:pallas backend takes weights/biases/"
                            f"inputs/quant/norms/check/seed/faults, "
                            f"got {sorted(kw)}")
        from ..core import ref
        t0 = time.perf_counter()
        cg = artifact.cg
        if weights is None:
            if biases is not None or inputs is not None:
                raise TypeError("pass weights+biases+inputs together "
                                "or none of them")
            batch = artifact.options.resolved_batch()
            weights, biases, inputs = ref.random_init(cg, batch=batch,
                                                      seed=seed)
        else:
            batch = int(inputs.shape[0])
        if quant is None:
            quant = ref.auto_quant(cg, weights, biases, inputs, norms=norms)
        # ``faults`` (a repro.faults.FaultSet) corrupts both oracles
        # identically, so the bit-equality check stays meaningful on
        # faulty runs — it validates the kernel, not the fault model
        with span("repro.ref.forward"):
            outs = ref.run_reference(cg, weights, biases, quant, inputs,
                                     matmul=_pallas_matmul, faults=faults,
                                     norms=norms)
        if check:
            with span("repro.ref.oracle") as sp:
                tally = ref.ContractionTally()
                want = ref.run_reference(cg, weights, biases, quant,
                                         inputs, matmul=tally,
                                         faults=faults, norms=norms)
                sp.set_metadata(f64=tally.f64)
                for gid, arr in want.items():
                    got = outs[gid]
                    if got.shape != arr.shape or not np.array_equal(got,
                                                                    arr):
                        raise AssertionError(
                            f"func:pallas mismatch on group {gid}: pallas "
                            f"oracle != numpy oracle "
                            f"(shapes {got.shape} vs {arr.shape})")
        # a functional-validation pass carries no timing claim
        return EvalReport(backend=self.name, cycles=0.0,
                          energy={"total": 0.0}, throughput_sps=0.0,
                          batch=batch,
                          wall_s=time.perf_counter() - t0, outputs=outs)


def _pallas_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """INT32-valued (int8-ranged) ``a @ b`` on the bit-serial kernel."""
    import jax.numpy as jnp

    from ..kernels.ops import cim_mvm
    (m, k), n = a.shape, b.shape[1]
    with span("repro.kernel.cim_mvm", m=m, k=k, n=n):
        return np.asarray(cim_mvm(jnp.asarray(a, jnp.int8),
                                  jnp.asarray(b, jnp.int8)))


BACKENDS: Dict[str, Backend] = {}


def register_backend(b: Backend, *aliases: str,
                     replace: bool = False) -> Backend:
    for key in (b.name,) + aliases:
        if key in BACKENDS and not replace:
            raise ValueError(f"backend {key!r} already registered")
        BACKENDS[key] = b
    return b


register_backend(AnalyticBackend())
register_backend(TraceBackend())
register_backend(SimulatorBackend("perf"), "perf")
register_backend(SimulatorBackend("func"))
register_backend(PallasFuncBackend())


def resolve_backend(backend: Union[str, Backend, None],
                    fidelity: str = "analytic") -> Backend:
    """Name | instance | None (-> the fidelity's default backend)."""
    if backend is None:
        backend = backend_for_fidelity(fidelity)
    if isinstance(backend, str):
        try:
            return BACKENDS[backend]
        except KeyError:
            raise KeyError(f"unknown backend {backend!r}; registered: "
                           f"{sorted(BACKENDS)}") from None
    if isinstance(backend, Backend):
        return backend
    raise TypeError(f"backend must be a name or Backend instance, "
                    f"got {type(backend).__name__}")


def backend_for_fidelity(fidelity: str) -> str:
    """CompileOptions.fidelity -> default backend name."""
    return {"analytic": "analytic", "trace": "trace",
            "simulate": "simulate", "func": "func"}[fidelity]
