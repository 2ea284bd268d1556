"""The :mod:`repro.flow` pipeline: ``compile(workload, chip, options)``.

Replaces the ad-hoc ``partition() -> compile_model() -> Simulator()``
call chain with one stable entry point:

    art = repro.flow.compile("resnet18", chip,
                             CompileOptions(strategy="dp",
                                            workload_kw={"res": 112}))
    report = art.evaluate(backend="analytic")      # or "simulate"/"func"

The pipeline is a chain of registered passes (condense ->
``partition:<strategy>`` -> codegen-on-demand), each instrumented with
wall time and a one-line IR summary (``Artifact.describe()``), and each
memoized in an LRU cache keyed by ``(workload, chip, options-prefix)``
— only the option fields a pass declares in ``depends`` enter its key.
A re-compile at a different *fidelity* therefore reuses the
already-computed ``PartitionResult`` instead of re-partitioning, which
is what makes cross-fidelity promotions (analytic screen -> simulator
validation) cheap.
"""

from __future__ import annotations

import hashlib
import json
import os
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..core.arch import ChipConfig
from ..core.codegen import CompiledModel
from ..core.graph import CondensedGraph, Graph
from ..core.partition import PartitionResult
from ..obs import span
from .backends import Backend, EvalReport, resolve_backend
from .diskcache import ENV_VAR as _CACHE_ENV
from .diskcache import PassDiskCache
from .options import CompileOptions
from .passes import (CodegenPass, Pass, PassRecord, PipelineContext,
                     get_pass, partition_pass_name)

__all__ = ["Artifact", "Pipeline", "compile", "compile_many",
           "default_pipeline", "workload_fingerprint"]


def workload_fingerprint(workload: Any) -> str:
    """Structural identity of a workload for pass-cache keying.

    Named workloads key by name (geometry lives in ``workload_kw``,
    which the condense pass declares as a dependency); graph objects key
    by a digest of their op (or group) structure, so two separately
    built but identical graphs share cache entries.
    """
    if isinstance(workload, str):
        return f"name:{workload}"

    def op_desc(g: Graph) -> list:
        return [(op.idx, op.name, op.kind, tuple(op.inputs),
                 tuple(op.out_shape), sorted(op.attrs.items()),
                 op.gemm_m, op.gemm_k, op.gemm_n, op.groups)
                for op in g.ops]

    if isinstance(workload, Graph):
        desc: Any = op_desc(workload)
        kind = "graph"
    elif isinstance(workload, CondensedGraph):
        # group geometry always enters the digest: two condensed graphs
        # over the same source but with different group records (e.g.
        # tensor-parallel shards) must not share cache entries
        desc = (op_desc(workload.source)
                if workload.source is not None else None,
                [(g.idx, g.name, tuple(g.preds), g.gemm_m, g.gemm_k,
                  g.gemm_n, g.groups, g.macs, g.weight_bytes,
                  g.in_bytes, g.out_bytes,
                  sorted(g.vector_work.items()))
                 for g in workload])
        kind = "cg"
    else:
        raise TypeError(f"workload must be a name, Graph or "
                        f"CondensedGraph, got {type(workload).__name__}")
    blob = repr((workload.name, desc)).encode()
    return f"{kind}:{hashlib.sha256(blob).hexdigest()}"


def _chip_fingerprint(chip: ChipConfig) -> str:
    d = chip.to_dict()
    d.pop("name", None)          # labels are cosmetic
    blob = json.dumps(d, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


# ---------------------------------------------------------------------------
# Artifact
# ---------------------------------------------------------------------------


@dataclass
class Artifact:
    """The result of :func:`compile`: partitioned (and, on demand,
    fully code-generated) model plus the instrumented pass trace."""

    workload: Any
    chip: ChipConfig
    options: CompileOptions
    cg: CondensedGraph
    partition: PartitionResult
    trace: List[PassRecord] = field(default_factory=list)
    _pipeline: Optional["Pipeline"] = None
    _chain_key: str = ""         # cache-key prefix up to the partition
    _model: Optional[CompiledModel] = None

    # -- lazy codegen ---------------------------------------------------------

    @property
    def model(self) -> Optional[CompiledModel]:
        """The compiled ISA streams, or ``None`` before codegen ran."""
        return self._model

    def ensure_model(self) -> CompiledModel:
        """Run (or fetch from cache) the codegen pass."""
        if self._model is None:
            ctx = PipelineContext(workload=self.workload, chip=self.chip,
                                  options=self.options, cg=self.cg,
                                  partition=self.partition)
            pipe = self._pipeline or default_pipeline()
            out, rec, _ = pipe._run_pass(get_pass("codegen"), ctx,
                                         self._chain_key)
            self._model = out
            self.trace.append(rec)
        return self._model

    # -- evaluation -----------------------------------------------------------

    def evaluate(self, backend: Union[str, Backend, None] = None,
                 **kw: Any) -> EvalReport:
        """Score this artifact on a backend (default: the one matching
        ``options.fidelity``)."""
        b = resolve_backend(backend, self.options.fidelity)
        return b.evaluate(self, **kw)

    # -- conveniences ---------------------------------------------------------

    def replace_options(self, **kw: Any) -> "Artifact":
        """This artifact under tweaked *evaluation* options — the
        compiled partition is shared, nothing re-runs.  Fields that
        feed codegen (``batch``, ``quant``, ``strict_lmem``) drop the
        cached model so ``ensure_model`` re-lowers on demand; fields
        that determine the partition itself (``strategy``, ``params``,
        ``workload_kw``) cannot be swapped under a finished compile —
        re-run :func:`compile` for those."""
        import dataclasses as _dc
        stale = {"strategy", "params", "workload_kw"} & set(kw)
        if stale:
            raise ValueError(
                f"{sorted(stale)} change the partition; recompile via "
                f"flow.compile(...) instead of replace_options")
        opts = self.options.replace(**kw)
        keep_model = self._model if not (
            {"batch", "quant", "strict_lmem"} & set(kw)) else None
        return _dc.replace(self, options=opts, trace=list(self.trace),
                           _model=keep_model)

    def build_gmem_image(self, weights, biases, inputs,
                         norms=None) -> np.ndarray:
        return self.ensure_model().build_gmem_image(weights, biases,
                                                    inputs, norms)

    def output_addr(self, gid: int, sample: int) -> Tuple[int, int]:
        return self.ensure_model().output_addr(gid, sample)

    @property
    def total_instrs(self) -> int:
        return self.ensure_model().total_instrs

    def pass_record(self, name: str) -> Optional[PassRecord]:
        """Latest trace record for a pass (``"partition"`` matches the
        strategy-qualified partition pass)."""
        for rec in reversed(self.trace):
            if rec.name == name or (name == "partition"
                                    and rec.name.startswith("partition:")):
                return rec
        return None

    def describe(self) -> str:
        head = (f"flow artifact: '{self.cg.name}' on "
                f"'{self.chip.name}' — {self.options.describe()}")
        return "\n".join([head] + [r.describe() for r in self.trace])


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


class Pipeline:
    """Pass runner with a two-tier (LRU + optional disk) output cache.

    One pipeline's cache is shared across all its ``compile()`` calls;
    the module-level :func:`default_pipeline` gives every caller in a
    process cross-fidelity partition reuse for free.  ``cache_size=0``
    disables caching.  The default cap is sized for full design-space
    sweeps (~1k chips x strategies; cached ``PartitionResult`` objects
    are a few KB each — codegen outputs are never cached) so an
    analytic screen's partitions survive until the simulator
    promotion.

    ``disk_cache`` (a directory path or :class:`PassDiskCache`) adds a
    persistent tier below the LRU, shared *across processes*: explore's
    pool workers — and tomorrow's re-run of the same sweep — skip
    re-partitioning anything any process already partitioned.
    """

    def __init__(self, cache_size: int = 8192,
                 disk_cache: Union[str, PassDiskCache, None] = None
                 ) -> None:
        self.cache_size = int(cache_size)
        self._cache: "OrderedDict[str, Any]" = OrderedDict()
        if isinstance(disk_cache, str):
            disk_cache = PassDiskCache(disk_cache)
        self.disk = disk_cache
        self.hits = 0
        self.misses = 0

    # -- cache ----------------------------------------------------------------

    def _cache_get(self, key: str) -> Tuple[bool, Any]:
        if key in self._cache:
            self._cache.move_to_end(key)
            self.hits += 1
            return True, self._cache[key]
        if self.disk is not None:
            ok, out = self.disk.get(key)
            if ok:
                self.hits += 1
                self._mem_put(key, out)     # promote to the hot tier
                return True, out
        self.misses += 1
        return False, None

    def _mem_put(self, key: str, value: Any) -> None:
        if self.cache_size <= 0:
            return
        self._cache[key] = value
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)

    def _cache_put(self, key: str, value: Any) -> None:
        self._mem_put(key, value)
        if self.disk is not None:
            self.disk.put(key, value)

    def cache_info(self) -> Dict[str, int]:
        info = {"entries": len(self._cache), "hits": self.hits,
                "misses": self.misses}
        if self.disk is not None:
            info["disk_hits"] = self.disk.hits
            info["disk_misses"] = self.disk.misses
        return info

    def clear_cache(self) -> None:
        self._cache.clear()

    # -- pass execution -------------------------------------------------------

    def _run_pass(self, p: Pass, ctx: PipelineContext,
                  prev_key: str) -> Tuple[Any, PassRecord, str]:
        import time
        subset = ctx.options.subset_key(p.depends)
        key = hashlib.sha256(
            f"{prev_key}|{p.name}|{subset}".encode()).hexdigest()
        # the span and ``wall_s`` time one interval: ``describe()`` and
        # a profiler trace agree on what the pass cost
        with span(f"repro.flow.{p.name}") as sp:
            t0 = time.perf_counter()
            cached, out = (self._cache_get(key) if p.cacheable
                           else (False, None))
            sp.set_metadata(cached=cached)
            if not cached:
                out = p.run(ctx)
                if p.cacheable:
                    self._cache_put(key, out)
            sp.set_metadata(**p.counters(out))
            dump_path = None
            if ctx.options.dump_dir:  # dump cache hits too — the dump
                dump_path = p.write_dump(out, ctx.options.dump_dir, key)
                # dir may differ from (or postdate) the run that filled
                # the cache
            p.apply(ctx, out)
            wall_s = time.perf_counter() - t0
        rec = PassRecord(name=p.name, wall_s=wall_s,
                         cached=cached, summary=p.summarize(out),
                         key=key[:16], dump_path=dump_path)
        return out, rec, key

    # -- compilation ----------------------------------------------------------

    def compile(self, workload: Any, chip: ChipConfig,
                options: Optional[CompileOptions] = None,
                **kw: Any) -> Artifact:
        """Compile ``workload`` for ``chip`` under ``options``.

        Extra keyword arguments are folded into the options
        (``compile(cg, chip, strategy="dp", batch=2)``).  Codegen runs
        eagerly for simulator fidelities and lazily (on
        ``Artifact.ensure_model`` / a simulator backend) otherwise.
        """
        return self.compile_many(workload, [chip], options, **kw)[0]

    def compile_many(self, workload: Any, chips: Sequence[ChipConfig],
                     options: Optional[CompileOptions] = None,
                     **kw: Any) -> List[Artifact]:
        """Compile one workload against N chips in a single invocation.

        The condense pass runs (or cache-hits) exactly once; only the
        chip-dependent partition pass repeats per candidate.  This is
        the batched analytic-evaluation path of arch sweeps: the shared
        machine model is O(1) to derive per chip, so the marginal cost
        of an extra candidate is one partition.
        """
        if options is None:
            options = CompileOptions(**kw)
        elif kw:
            options = options.replace(**kw)

        if options.system is not None:
            return [self._compile_system(workload, chip, options)
                    for chip in chips]

        try:
            part_pass = get_pass(partition_pass_name(options.strategy))
        except KeyError:
            raise KeyError(
                f"unknown strategy {options.strategy!r}: no "
                f"{partition_pass_name(options.strategy)!r} pass "
                f"registered") from None

        # condense is chip-independent: keying it on the workload alone
        # lets one cache entry serve every chip in an arch sweep.  The
        # chip fingerprint enters the chain between condense and the
        # (chip-dependent) partition/codegen passes.
        base = hashlib.sha256(
            workload_fingerprint(workload).encode()).hexdigest()
        ctx0 = PipelineContext(workload=workload,
                               chip=chips[0] if chips else None,
                               options=options)
        _, cond_rec, cond_key = self._run_pass(get_pass("condense"),
                                               ctx0, base)

        arts: List[Artifact] = []
        for chip in chips:
            ctx = PipelineContext(workload=workload, chip=chip,
                                  options=options, cg=ctx0.cg)
            key = hashlib.sha256(
                f"{cond_key}|chip:{_chip_fingerprint(chip)}"
                .encode()).hexdigest()
            _, rec, key = self._run_pass(part_pass, ctx, key)
            art = Artifact(workload=workload, chip=chip,
                           options=options, cg=ctx.cg,
                           partition=ctx.partition,
                           trace=[cond_rec, rec],
                           _pipeline=self, _chain_key=key)
            # only the simulator fidelities need ISA streams; analytic
            # and trace evaluate straight off the partition
            if options.fidelity in ("simulate", "func"):
                art.ensure_model()
            arts.append(art)
        return arts


    # -- multi-chip (repro.system) --------------------------------------------

    def _compile_system(self, workload: Any, chip: ChipConfig,
                        options: CompileOptions) -> Any:
        """The ``options.system`` path: condense once, run the
        ``system:<mode>`` partition pass, then compile each chip slice
        through the ordinary single-chip pipeline (``system=None``) —
        a 1x1 mesh therefore produces an inner artifact bit-identical
        to the classic path.  Returns a
        :class:`repro.system.SystemArtifact`.
        """
        # imported lazily: repro.system imports repro.flow at module
        # level, so flow -> system must stay function-local
        from ..system import SystemArtifact
        from ..system.passes import system_pass_name

        sysc = options.system
        if sysc.parallel == "tensor" and sysc.n_chips > 1 \
                and options.fidelity in ("simulate", "func"):
            raise ValueError(
                "tensor-parallel plans support analytic/trace fidelity "
                "only (shards are group-level scaled condensed graphs "
                "with no per-shard ISA streams); use "
                "parallel='pipeline' for simulator fidelities")

        base = hashlib.sha256(
            workload_fingerprint(workload).encode()).hexdigest()
        ctx0 = PipelineContext(workload=workload, chip=chip,
                               options=options)
        _, cond_rec, cond_key = self._run_pass(get_pass("condense"),
                                               ctx0, base)
        ctx = PipelineContext(workload=workload, chip=chip,
                              options=options, cg=ctx0.cg)
        key = hashlib.sha256(
            f"{cond_key}|chip:{_chip_fingerprint(chip)}"
            .encode()).hexdigest()
        plan, rec, key = self._run_pass(
            get_pass(system_pass_name(sysc.parallel)), ctx, key)

        inner = options.replace(system=None)
        arts = [self.compile(sl.workload if sl.workload is not None
                             else workload, chip, inner)
                for sl in plan.slices]
        return SystemArtifact(workload=workload, chip=chip,
                              options=options, cg=ctx.cg, plan=plan,
                              chips=arts, trace=[cond_rec, rec])


_DEFAULT_PIPELINE: Optional[Pipeline] = None


def default_pipeline() -> Pipeline:
    """The process-wide pipeline (shared pass-output cache).

    When the ``REPRO_FLOW_CACHE`` environment variable names a
    directory, the pipeline also persists pass outputs there —
    processes pointed at the same directory (e.g. explore pool
    workers) share partitions.  Set before first use; the pipeline is
    created once per process.
    """
    global _DEFAULT_PIPELINE
    if _DEFAULT_PIPELINE is None:
        _DEFAULT_PIPELINE = Pipeline(
            disk_cache=os.environ.get(_CACHE_ENV) or None)
    return _DEFAULT_PIPELINE


def compile(workload: Any, chip: ChipConfig,
            options: Optional[CompileOptions] = None, *,
            pipeline: Optional[Pipeline] = None,
            **kw: Any) -> Artifact:
    """The stable compile entry point (see :class:`Pipeline.compile`).

    Uses the process-wide default pipeline unless one is given, so
    successive compiles of the same (workload, chip, options-prefix)
    hit the pass cache.
    """
    return (pipeline or default_pipeline()).compile(workload, chip,
                                                    options, **kw)


def compile_many(workload: Any, chips: Sequence[ChipConfig],
                 options: Optional[CompileOptions] = None, *,
                 pipeline: Optional[Pipeline] = None,
                 **kw: Any) -> List[Artifact]:
    """Batched compile: one workload, N candidate chips, one condense
    (see :meth:`Pipeline.compile_many`)."""
    return (pipeline or default_pipeline()).compile_many(
        workload, chips, options, **kw)
