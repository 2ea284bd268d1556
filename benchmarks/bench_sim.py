"""Simulator performance trajectory: committed wall-time + cycles.

Measures the fidelity ladder on the golden workloads (tiny_cnn and
resnet18@112, batch 4, default chip) and records, per workload:

* cycles for analytic / trace / perf (and func where the model is
  functionally valid — resnet18@112 overflows local-memory segments on
  the default chip, so only its timing fidelities run);
* wall seconds for analytic, trace, the perf simulator on all three
  engines (``vector`` = pre-decoded numpy replay, ``scalar`` =
  interpreter, ``jax`` = jitted XLA stage engine), plus the vector
  engine's *cold* cost (decode tables stripped, so pack + decode
  + replay — the price codegen normally pays when it ships the tables);
* the vector-vs-scalar speedup per workload and its geomean;
* the *fleet* section: a 256-point unit-latency DSE sweep (one compiled
  program, ``explore.FleetEvaluator`` vmapped batching) against the
  pool-parallel per-point baseline — the batched evaluator must stay
  >= ``FLEET_MIN_SPEEDUP`` x faster;
* the *func_pallas* section: the Pallas bit-serial oracle backend
  validated bit-exact against the numpy oracle at resnet18@224.

Wall measurement protocol: engines are interleaved and the min over
``--reps`` repeats is kept, so CPU-share throttling hits both engines
alike and the committed *speedups* stay machine-comparable even though
absolute seconds are not.

The committed golden is ``BENCH_simulator.json`` at the repo root — the
perf trajectory tracked across PRs.  ``--smoke`` re-measures and fails
when cycles drift at all (machine-model/codegen change: regenerate with
``--update-golden`` and commit the diff) or when the measured speedup
falls more than 20% below the committed one AND below the absolute
``ABS_MIN_SPEEDUP`` floor — the same-machine ratio is stable, but a
different CPU/numpy build legitimately shifts it, so only missing both
bars indicates a real wall-time regression in the vectorized engine.

    PYTHONPATH=src python -m benchmarks.bench_sim [--smoke]
        [--update-golden] [--reps N] [--json PATH]
        [--engine {all,scalar,vector,jax}]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import warnings
from typing import Dict, List, Optional

import numpy as np

GOLDEN_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_simulator.json")

# the golden workloads: (model, workload_kw, strategy, func-valid)
WORKLOADS = (
    ("tiny_cnn", {}, "dp", True),
    ("tiny_cnn", {}, "generic", True),
    ("resnet18", {"res": 112}, "dp", False),
    ("resnet18", {"res": 112}, "generic", False),
    # dynamic-weight attention (weight-source abstraction): guards the
    # transformer lowering path against regressing to compile errors
    ("transformer", {"n_layers": 1, "d_model": 128, "n_heads": 4,
                     "seq": 16, "vocab": 64}, "dp", True),
)
BATCH = 4
# fail --smoke when the measured speedup drops below this fraction of
# the committed golden's (the ">20% wall regression" gate).  The
# vector/scalar ratio is stable on ONE machine (engines are timed
# interleaved) but legitimately varies across CPUs/numpy builds, so a
# machine whose healthy ratio clears ABS_MIN_SPEEDUP passes even when
# it cannot reproduce the committed golden's ratio — only a genuine
# engine regression fails both bars.
SPEEDUP_TOLERANCE = 0.8
ABS_MIN_SPEEDUP = 4.0
# the vmapped fleet evaluator must beat the pool-parallel per-point
# baseline by at least this factor on the 256-point timing sweep.  The
# fleet's cost is one XLA compile + ~3ms/point of replay while the
# baseline pays a full compile+simulate pipeline per point, so the
# ratio *grows* with sweep size; the smoke gate normalizes the
# baseline to aggregate CPU cost (wall x pool width) so a many-core CI
# runner is judged on compute spent, not on how wide its pool is.
FLEET_MIN_SPEEDUP = 3.0
FLEET_POINTS = 256


def _strip_tables(model) -> None:
    """Drop the decode tables codegen attached (cold-start measurement)."""
    for sp in model.stages:
        for p in sp.programs.values():
            if hasattr(p, "_packed"):
                del p._packed


def _min_wall(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_rows(reps: int = 3) -> List[Dict]:
    from repro import flow
    from repro.core.arch import default_chip
    from repro.core.mapping import CostParams
    from repro.core.simulator import Simulator

    chip = default_chip()
    rows: List[Dict] = []
    for model, kw, strategy, func_ok in WORKLOADS:
        t0 = time.perf_counter()
        art = flow.compile(
            model, chip,
            flow.CompileOptions(strategy=strategy,
                                params=CostParams(batch=BATCH),
                                workload_kw=kw or None))
        ana = art.evaluate("analytic")
        tr = art.evaluate("trace")
        cm = art.ensure_model()      # codegen + decode tables
        compile_s = time.perf_counter() - t0

        vec_sim = Simulator(chip, cm.isa, engine="vector")
        scal_sim = Simulator(chip, cm.isa, engine="scalar")
        jax_sim = Simulator(chip, cm.isa, engine="jax")
        vec = vec_sim.run_model(cm)           # warm + correctness ref
        scal = scal_sim.run_model(cm)
        jx = jax_sim.run_model(cm)            # warm (jit compiles here)
        for name, rep in (("vectorized", vec), ("jax", jx)):
            if (rep.cycles != scal.cycles or rep.events != scal.events
                    or rep.unit_busy != scal.unit_busy
                    or rep.instrs != scal.instrs):
                raise AssertionError(
                    f"{model}/{strategy}: {name} engine diverged from "
                    f"the scalar interpreter (cycles {rep.cycles} vs "
                    f"{scal.cycles})")

        # interleaved min-of-reps: throttling hits all engines alike
        wall_v, wall_s, wall_j = (float("inf"),) * 3
        for _ in range(reps):
            t0 = time.perf_counter()
            vec_sim.run_model(cm)
            wall_v = min(wall_v, time.perf_counter() - t0)
            t0 = time.perf_counter()
            scal_sim.run_model(cm)
            wall_s = min(wall_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax_sim.run_model(cm)
            wall_j = min(wall_j, time.perf_counter() - t0)

        def cold() -> None:
            _strip_tables(cm)
            Simulator(chip, cm.isa, engine="vector").run_model(cm)

        wall_cold = _min_wall(cold, max(1, reps - 1))
        cm2 = art.ensure_model()     # re-attach tables for later users
        for sp in cm2.stages:
            for p in sp.programs.values():
                p.pack(cm2.isa)

        row = {
            "workload": model, "kw": kw, "strategy": strategy,
            "batch": BATCH, "instrs": int(vec.instrs),
            "compile_s": round(compile_s, 3),
            "cycles": {
                "analytic": round(ana.cycles, 1),
                "trace": round(tr.cycles, 1),
                "perf": vec.cycles,
            },
            "wall_s": {
                "analytic": round(ana.wall_s, 5),
                "trace": round(tr.wall_s, 5),
                "perf_vector": round(wall_v, 5),
                "perf_vector_cold": round(wall_cold, 5),
                "perf_scalar": round(wall_s, 5),
                "perf_jax": round(wall_j, 5),
            },
            "speedup": round(wall_s / wall_v, 2),
            "speedup_cold": round(wall_s / wall_cold, 2),
            "speedup_jax": round(wall_s / wall_j, 2),
        }
        if func_ok:
            img = np.zeros(cm.layout.size, dtype=np.int8)
            t0 = time.perf_counter()
            fn = Simulator(chip, cm.isa, mode="func").run_model(
                cm, gmem_image=img)
            row["wall_s"]["func"] = round(time.perf_counter() - t0, 5)
            row["cycles"]["func"] = fn.cycles
        rows.append(row)
    return rows


def profile_engine(engine: str, reps: int = 3) -> List[Dict]:
    """Time one perf engine alone on the golden workloads (the
    ``--engine`` path — a profiling aid, no golden interplay)."""
    from repro import flow
    from repro.core.arch import default_chip
    from repro.core.mapping import CostParams
    from repro.core.simulator import Simulator

    chip = default_chip()
    rows = []
    for model, kw, strategy, _func_ok in WORKLOADS:
        art = flow.compile(
            model, chip,
            flow.CompileOptions(strategy=strategy,
                                params=CostParams(batch=BATCH),
                                workload_kw=kw or None))
        cm = art.ensure_model()
        sim = Simulator(chip, cm.isa, engine=engine)
        rep = sim.run_model(cm)              # warm
        wall = _min_wall(lambda: sim.run_model(cm), reps)
        rows.append({"workload": model, "kw": kw, "strategy": strategy,
                     "engine": engine, "cycles": rep.cycles,
                     "wall_s": round(wall, 5)})
    return rows


def fleet_points(n_points: int = FLEET_POINTS) -> List:
    """The unit-latency timing sweep both fleet sides evaluate."""
    from repro.explore import timing_space

    sp = timing_space(scalar_alu=(1, 2, 3, 4), router=(1, 2, 3, 4))
    return list(sp.points())[:n_points]


def bench_pool_baseline(pts: List) -> Dict:
    """The pool-parallel per-point pipeline over ``pts`` (numpy engine).

    It forks workers, so it runs before anything in this process
    touches JAX: a child forked from a process that holds the device
    runtime inherits a chip it cannot use.
    """
    from repro.core.mapping import CostParams
    from repro.explore import ExplorationEngine

    pool = os.cpu_count() or 1
    base = ExplorationEngine("tiny_cnn", params=CostParams(batch=BATCH),
                             pool=pool, engine="auto")
    t0 = time.perf_counter()
    brecs = base.evaluate(pts, fidelity="simulate")
    return {"records": brecs, "wall_s": time.perf_counter() - t0,
            "pool": pool}


def bench_fleet(pts: List, baseline: Dict, reps: int = 1) -> Dict:
    """256-point unit-latency sweep at simulate fidelity: the vmapped
    fleet evaluator (one compile, batched XLA decode) vs the
    pool-parallel per-point pipeline measured by
    :func:`bench_pool_baseline`.

    The baseline compiles each timing point's own chip, so its results
    can legitimately diverge from the fleet's pinned-program semantics
    on points where a timing constant steers the partitioner — the
    sweep-level contract checked here is the all-defaults point, whose
    canonical chip IS its own chip.
    """
    from repro.core.mapping import CostParams
    from repro.explore import ExplorationEngine

    jx = ExplorationEngine("tiny_cnn", params=CostParams(batch=BATCH),
                           engine="jax")
    wall_fleet = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jrecs = jx.evaluate(pts, fidelity="simulate")
        wall_fleet = min(wall_fleet, time.perf_counter() - t0)
    assert all(r.ok for r in jrecs), [r.error for r in jrecs if not r.ok]

    brecs = baseline["records"]
    wall_pool = baseline["wall_s"]
    defaults = next(i for i, p in enumerate(pts)
                    if (p.scalar_alu_latency, p.vector_alu_latency,
                        p.weight_load_rows_per_cycle,
                        p.router_latency) == (1, 1, 1, 2))
    if jrecs[defaults].cycles != brecs[defaults].cycles:
        raise AssertionError(
            f"fleet diverged from the per-point baseline on the "
            f"all-defaults point: {jrecs[defaults].cycles} vs "
            f"{brecs[defaults].cycles}")
    return {
        "workload": "tiny_cnn", "batch": BATCH, "points": len(pts),
        "pool": baseline["pool"],
        "wall_s": {"fleet_jax": round(wall_fleet, 3),
                   "pool_baseline": round(wall_pool, 3)},
        "speedup": round(wall_pool / wall_fleet, 2),
    }


def bench_func_pallas(res: int = 224) -> Dict:
    """resnet18@``res`` through the ``func:pallas`` oracle backend —
    every MVM on the Pallas bit-serial kernel, asserted bit-exact
    against the pure-numpy oracle (check=True raises on mismatch)."""
    from repro import flow
    from repro.core.arch import default_chip

    art = flow.compile("resnet18", default_chip(), flow.CompileOptions(
        strategy="dp", batch=1, workload_kw={"res": res},
        fidelity="analytic"))
    rep = art.evaluate("func:pallas")
    return {"workload": "resnet18", "res": res, "batch": 1,
            "groups": len(rep.outputs), "bit_exact": True,
            "wall_s": round(rep.wall_s, 2)}


def _geomean(xs: List[float]) -> float:
    xs = [x for x in xs if x > 0]
    if not xs:
        return 0.0
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def to_doc(rows: List[Dict], fleet: Optional[Dict] = None,
           func_pallas: Optional[Dict] = None) -> Dict:
    doc = {
        "schema": 2,
        "chip": "default",
        "note": ("speedup = perf_scalar / perf_vector wall (speedup_jax "
                 "likewise), interleaved min-of-reps; *_cold includes "
                 "pack+decode (normally paid once at codegen); fleet = "
                 "vmapped batched DSE sweep vs pool-parallel per-point "
                 "baseline"),
        "rows": rows,
        "geomean_speedup": round(_geomean([r["speedup"] for r in rows]),
                                 2),
        "geomean_speedup_cold": round(
            _geomean([r["speedup_cold"] for r in rows]), 2),
        "geomean_speedup_jax": round(
            _geomean([r["speedup_jax"] for r in rows]), 2),
    }
    if fleet is not None:
        doc["fleet"] = fleet
    if func_pallas is not None:
        doc["func_pallas"] = func_pallas
    return doc


def report(doc: Dict) -> str:
    out = ["== simulator bench (default chip, batch 4) ==",
           f"{'workload':20s} {'strategy':8s} {'instrs':>8s} "
           f"{'perf cycles':>12s} {'scalar':>9s} {'vector':>9s} "
           f"{'cold':>9s} {'speedup':>8s}"]
    for r in doc["rows"]:
        w = r["wall_s"]
        name = r["workload"] + "".join(f"@{k}={v}"
                                       for k, v in sorted(r["kw"].items()))
        out.append(
            f"{name:20s} {r['strategy']:8s} {r['instrs']:8d} "
            f"{r['cycles']['perf']:12.0f} {w['perf_scalar']*1e3:8.1f}m "
            f"{w['perf_vector']*1e3:8.2f}m "
            f"{w['perf_vector_cold']*1e3:8.1f}m {r['speedup']:7.1f}x")
    out.append(f"geomean speedup: {doc['geomean_speedup']:.2f}x "
               f"(cold {doc['geomean_speedup_cold']:.2f}x, "
               f"jax {doc.get('geomean_speedup_jax', 0):.2f}x)")
    fl = doc.get("fleet")
    if fl:
        w = fl["wall_s"]
        out.append(
            f"fleet sweep ({fl['points']} timing points, "
            f"{fl['workload']}): vmapped {w['fleet_jax']:.2f}s vs "
            f"pool[{fl['pool']}] {w['pool_baseline']:.2f}s = "
            f"{fl['speedup']:.1f}x")
    fp = doc.get("func_pallas")
    if fp:
        out.append(
            f"func:pallas {fp['workload']}@{fp['res']}: "
            f"{fp['groups']} groups bit-exact vs numpy oracle in "
            f"{fp['wall_s']:.1f}s")
    return "\n".join(out)


def smoke_drift(doc: Dict, golden: Dict) -> List[str]:
    """Failures vs the committed golden (empty = clean)."""
    drift: List[str] = []
    key = lambda r: (r["workload"], json.dumps(r["kw"], sort_keys=True),
                     r["strategy"])                         # noqa: E731
    grows = {key(r): r for r in golden.get("rows", [])}
    for r in doc["rows"]:
        g = grows.pop(key(r), None)
        if g is None:
            drift.append(f"{key(r)}: not in golden")
            continue
        for fid in sorted(set(r["cycles"]) | set(g["cycles"])):
            cyc = r["cycles"].get(fid)
            gc = g["cycles"].get(fid)
            if cyc is None or gc is None:
                drift.append(f"{key(r)}.cycles.{fid}: "
                             f"{'missing' if cyc is None else 'new'} "
                             f"vs golden")
            elif cyc != gc:
                drift.append(f"{key(r)}.cycles.{fid}: {gc} -> {cyc}")
        if r["instrs"] != g["instrs"]:
            drift.append(f"{key(r)}.instrs: {g['instrs']} -> "
                         f"{r['instrs']}")
        floor = g["speedup"] * SPEEDUP_TOLERANCE
        # the absolute floor halves for rows whose committed ratio is
        # itself small (short-program rows — e.g. the transformer block
        # — measure noisier, and a 4x floor leaves them no slack)
        abs_floor = min(ABS_MIN_SPEEDUP, 0.5 * g["speedup"])
        if r["speedup"] < floor and r["speedup"] < abs_floor:
            drift.append(
                f"{key(r)}.speedup: {r['speedup']}x < {floor:.1f}x "
                f"(>20% wall-time regression vs golden "
                f"{g['speedup']}x) and below the absolute "
                f"{abs_floor:.1f}x floor")
    drift.extend(f"{k}: only in golden" for k in grows)
    fl = doc.get("fleet")
    gfl = golden.get("fleet")
    if fl is None:
        if gfl is not None:
            drift.append("fleet: section missing (golden has one)")
    else:
        # pool-normalized: a wider pool legitimately shrinks the wall
        # ratio, so gate on the baseline's aggregate CPU cost
        # (wall x pool width) -- equal to the wall ratio on the
        # single-core machine the golden is committed from
        norm = fl["speedup"] * fl.get("pool", 1)
        gnorm = ((gfl["speedup"] * gfl.get("pool", 1))
                 if gfl else FLEET_MIN_SPEEDUP)
        if norm < FLEET_MIN_SPEEDUP and norm < SPEEDUP_TOLERANCE * gnorm:
            drift.append(
                f"fleet.speedup: {fl['speedup']}x over a "
                f"{fl.get('pool', 1)}-wide pool "
                f"({norm:.2f}x CPU-normalized) < the "
                f"{FLEET_MIN_SPEEDUP}x floor and >20% below the "
                f"golden's {gnorm:.2f}x (vmapped batched evaluator "
                f"vs pool-parallel baseline, {fl['points']} points)")
    fp = doc.get("func_pallas")
    if fp is None and golden.get("func_pallas") is not None:
        drift.append("func_pallas: section missing (golden has one)")
    return drift


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="gate against the committed golden (CI job)")
    ap.add_argument("--update-golden", action="store_true",
                    help=f"rewrite {GOLDEN_PATH}")
    ap.add_argument("--reps", type=int, default=None,
                    help="timing repeats per engine (default: 3, "
                         "smoke: 2)")
    ap.add_argument("--json", default="results/bench_simulator.json",
                    help="also write the measured doc here "
                         "('' to skip)")
    ap.add_argument("--engine",
                    choices=("all", "scalar", "vector", "jax"),
                    default="all",
                    help="profile one perf engine only (skips the "
                         "golden/fleet/func sections)")
    args = ap.parse_args(argv)
    reps = args.reps or (2 if args.smoke else 3)

    if args.engine != "all":
        if args.smoke or args.update_golden:
            raise SystemExit("--engine profiles one engine only; it "
                             "cannot be combined with --smoke / "
                             "--update-golden")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rows = profile_engine(args.engine, reps=reps)
        for r in rows:
            name = r["workload"] + "".join(
                f"@{k}={v}" for k, v in sorted(r["kw"].items()))
            print(f"{name:20s} {r['strategy']:8s} "
                  f"[{r['engine']}] {r['cycles']:12.0f} cycles  "
                  f"{r['wall_s'] * 1e3:8.2f}ms")
        return 0

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        pts = fleet_points()
        baseline = bench_pool_baseline(pts)     # forks: before any JAX
        rows = bench_rows(reps=reps)
        doc = to_doc(rows, fleet=bench_fleet(pts, baseline, reps=1),
                     func_pallas=bench_func_pallas())
    print(report(doc))
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"wrote {args.json}")
    if args.update_golden:
        with open(GOLDEN_PATH, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"golden updated: {GOLDEN_PATH}")
        return 0
    if args.smoke:
        try:
            with open(GOLDEN_PATH) as f:
                golden = json.load(f)
        except FileNotFoundError:
            print(f"golden {GOLDEN_PATH} missing "
                  f"(generate with --update-golden)")
            return 1
        drift = smoke_drift(doc, golden)
        if drift:
            print("SIMULATOR BENCH DRIFT vs committed golden:")
            for d in drift:
                print(f"  {d}")
            print("if the cycle change is intentional, regenerate with "
                  "`python -m benchmarks.bench_sim --update-golden` "
                  "and commit the diff")
            return 1
        print("golden: clean "
              f"(committed geomean {golden['geomean_speedup']}x, "
              f"measured {doc['geomean_speedup']}x)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
