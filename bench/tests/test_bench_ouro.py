"""The cells this benchmark gained with the looped LM: a tiny Ouro-
shaped configuration through a ``sweep16``-shaped mix, the structural
sweep's entry on ``tiny_cnn``, the reader of repeated stage calls, and
the Ouro cut's plain reference against the system's own oracle."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import registry
import tracereduce
from conftest import BENCH, DATA, TINY

OURO = "ouro-tiny"
CELLS = {f"{OURO}.sweep16": (OURO, "sweep16"),
         f"{TINY}.struct-sweep": (TINY, "struct-sweep")}


@pytest.fixture
def cells(tmp_path, monkeypatch):
    """``run(cell)``: one run of a cell of a throw-away checkout that
    holds the tiny Ouro and tiny_cnn configurations under the new mixes
    (CPU, no look for a chip)."""
    import jax

    import harness
    from repro import flow
    from repro.flow.diskcache import ENV_VAR

    bench = os.path.join(str(tmp_path), "bench")
    os.makedirs(os.path.join(bench, "configs"))
    for d in ("entries", "e2e", "metrics", "traffic"):
        os.symlink(os.path.join(BENCH, d), os.path.join(bench, d))
    for name in (OURO, TINY):
        with open(os.path.join(DATA, name + ".json")) as f:
            cfg = f.read()
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            f.write(cfg)
    spec = {"workloads": [{"name": c, "config": cfg, "traffic": mix,
                           "chips": 1, "why": "CPU tests"}
                          for c, (cfg, mix) in CELLS.items()],
            "end_to_end": [{"name": "points_per_s", "unit": "points/s",
                            "better": "higher", "bound": 0.09,
                            "source": "host_clock",
                            "workloads": sorted(CELLS)},
                           {"name": "setup_s", "unit": "s",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock"}],
            "per_layer": []}
    with open(os.path.join(str(tmp_path), "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    init = registry.Cell.__init__

    def cell_init(self, root, name, bench_dir=registry.BENCH_DIR):
        init(self, root, name, bench)
    monkeypatch.setattr(registry.Cell, "__init__", cell_init)
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    disk = flow.default_pipeline().disk
    env = os.environ.get(ENV_VAR)

    def run(cell):
        import time
        return harness.run_cell(str(tmp_path), cell, 2**31 + 29, 0.0,
                                False, time.perf_counter(),
                                check_device=False)
    yield run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
    flow.default_pipeline().disk = disk
    if env is None:
        os.environ.pop(ENV_VAR, None)
    else:
        os.environ[ENV_VAR] = env


def _one_correct_request(out, points):
    assert out["correct"], out["checks"]
    assert (out["attempted"], out["failed"]) == (1, 0)
    assert out["window_compiles"] == 0
    assert sorted(out["checks"]) == ["cycles_gap", "energy_gap"]
    assert all(c["value"] == 0 == c["limit"]
               for c in out["checks"].values())
    assert sorted(out["metrics"]) == ["points_per_s", "setup_s"]
    assert out["metrics"]["points_per_s"]["value"] > 0
    assert len(points) == len(set(points))


def test_tiny_ouro_sweep16_matches_refsim(cells):
    seen = []
    from repro.explore import ExplorationEngine
    inner = ExplorationEngine.evaluate

    def spy(self, points, fidelity=None):
        seen.extend((p.weight_load_rows_per_cycle, p.vector_alu_latency)
                    for p in points)
        return inner(self, points, fidelity)
    mp = pytest.MonkeyPatch()
    mp.setattr(ExplorationEngine, "evaluate", spy)
    try:
        out = cells(f"{OURO}.sweep16")
    finally:
        mp.undo()
    _one_correct_request(out, seen)
    # the request is the whole 4 x 4 grid
    assert sorted(seen) == [(w, v) for w in (1, 2, 4, 8)
                            for v in (1, 2, 3, 4)]


def test_struct_sweep_compiles_each_point_and_checks_its_listing(cells):
    from repro.flow.pipeline import Pipeline
    compiled = []
    inner = Pipeline.compile

    def spy(self, workload, chip, options=None, **kw):
        compiled.append((chip.core.cim.macros_per_group, chip.noc.flit_bytes,
                         options.strategy))
        return inner(self, workload, chip, options, **kw)
    mp = pytest.MonkeyPatch()
    mp.setattr(Pipeline, "compile", spy)
    try:
        out = cells(f"{TINY}.struct-sweep")
    finally:
        mp.undo()
    # set-up: all 12 points; the window: one request of 4 of them; the
    # check: those 4 again
    assert len(compiled) == 12 + 4 + 4
    assert len(set(compiled[:12])) == 12
    _one_correct_request(out, compiled[12:16])
    assert set(compiled[12:16]) < set(compiled[:12])
    assert sorted(compiled[16:]) == sorted(compiled[12:16])


def _struct_requests(seed, n):
    """The points of ``n`` consecutive struct-sweep requests of a seed
    (the entry's compile and simulation stubbed out)."""
    import traffic
    with open(os.path.join(BENCH, "traffic", "struct-sweep.json")) as f:
        mix = json.load(f)
    mod = registry.load_module(os.path.join(BENCH, "entries",
                                            "structsweep.py"))
    rngs = traffic.streams(seed)
    entry = mod.Entry(SimpleNamespace(config={}, traffic=mix), rngs)
    entry._compile = lambda point: None
    entry._simulate = lambda point, cm: None
    stream = traffic.requests(mix, rngs["traffic"])
    out = []
    for _ in range(n):
        payload = next(stream)
        entry.request(payload)
        out.append([tuple(sorted(p.items())) for p in payload["points"]])
    return out


def test_struct_sweep_requests_cover_the_grid_every_three():
    reqs = _struct_requests(2**31 + 7, 6)
    assert all(len(r) == len(set(r)) == 4 for r in reqs)
    # 3 consecutive requests are the 12-point grid, then it starts over
    assert len(set(sum(reqs[:3], []))) == 12
    assert reqs[3:] == reqs[:3]
    # the order is the seed's
    assert _struct_requests(2**31 + 7, 3) == reqs[:3]
    assert _struct_requests(2**33 + 5, 3) != reqs[:3]


def test_struct_sweep_sees_a_stale_point(cells):
    """Every point simulated after the first returns the first one's
    statistics: the check reads not correct."""
    from repro.core.simulator import Simulator
    inner = Simulator.run_model
    first = []

    def stale(self, model, gmem_image=None):
        if not first:
            first.append(inner(self, model, gmem_image))
        return first[0]
    mp = pytest.MonkeyPatch()
    mp.setattr(Simulator, "run_model", stale)
    try:
        out = cells(f"{TINY}.struct-sweep")
    finally:
        mp.undo()
    assert not out["correct"], out["checks"]


def _repeat_run(host):
    events = {"devices": {"TPU:0": {"modules": [], "ops": []}},
              "host": [["bench.window", 10, 100],
                       ["bench.request", 10, 100]] + host}
    return SimpleNamespace(reduction=tracereduce.Reduction(events),
                           requests=[None])


def test_repeated_stage_calls_reader():
    read = registry.load_module(os.path.join(
        BENCH, "metrics", "repeated_stage_calls.sweep.py")).read
    call, mark = "repro.sim.device_call", "repro.sim.device_call.repeat"
    run = _repeat_run([[mark, 4, 0], [call, 5, 4],  # before the window
                       [call, 20, 10], [mark, 39, 0], [call, 40, 10],
                       [call, 60, 10], [mark, 79, 0], [call, 80, 10],
                       [mark, 119, 0], [call, 120, 5]])  # after it
    assert read(run) == pytest.approx(50.0)
    assert read(_repeat_run([[call, 20, 10]])) == 0.0
    # a program without device calls in the window reads nothing
    assert read(_repeat_run([["repro.sim.finish", 20, 10]])) is None


def test_ouro_reference_matches_the_system_oracle():
    """``configs/ouro-2.6b-s256.py`` (which imports nothing of the
    system) against ``core/ref.py`` at the tiny size, group by group."""
    from repro.core import ref, workloads
    from repro.core.codegen import QuantParams
    R = registry.load_module(os.path.join(BENCH, "configs",
                                          "ouro-2.6b-s256.py"))
    with open(os.path.join(BENCH, "configs", "ouro-2.6b-s256.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, OURO + ".json")) as f:
        kw = dict(json.load(f)["workload_kw"])
    cfg.update(hidden_size=kw["d_model"], intermediate_size=kw["d_ff"],
               seq=kw["seq"], num_attention_heads=kw["n_heads"],
               head_dim=kw["head_dim"], num_hidden_layers=kw["layers"],
               total_ut_steps=kw["loop_steps"])
    rng = np.random.default_rng(5)
    p = R.make_params(cfg, rng)
    x = R.make_state(cfg, rng)
    shifts = R.calibrate(cfg, p, x)
    want = R.forward(cfg, p, shifts, x)
    cg = workloads.build("arch", **kw).condense()
    sp = R.system_params(p, shifts, cg, QuantParams)
    got = ref.run_reference(cg, sp["weights"], {}, sp["quant"], x[None],
                            norms=sp["norms"])
    for g in cg:
        np.testing.assert_array_equal(got[g.idx][0].reshape(-1),
                                      want[g.name].reshape(-1),
                                      err_msg=g.name)
    assert R.nonzero_share(want) >= 0.5
    assert len(R.layers(cfg)) == len(cg)
