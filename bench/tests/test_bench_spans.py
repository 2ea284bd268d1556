"""The readers over the program's own spans (``spanlib``), on synthetic
events and on a traced run of each tiny cell."""

import gzip
import json
import os
from types import SimpleNamespace

import pytest

import registry
import spanlib
import tracereduce
from conftest import ROOT, TINY, TINY_CELLS


def _run(host, modules=(), requests=2):
    """A traced run of a 100 ns window (10..110) with these host events
    and device program executions."""
    events = {"devices": {"TPU:0": {"modules": [list(m) for m in modules],
                                    "ops": []}},
              "host": [["bench.window", 10, 100],
                       ["bench.request", 10, 100]] + [list(h) for h in host]}
    return SimpleNamespace(reduction=tracereduce.Reduction(events),
                           requests=[None] * requests)


def test_self_time_subtracts_nested_program_spans_only():
    run = _run([["repro.flow.codegen", 10, 20],
                ["repro.sim.device_call", 40, 30],
                ["np.asarray(jax.Array)", 50, 15],     # JAX's own event
                ["repro.sim.finish", 70, 20],
                ["repro.sim.replay", 75, 10],          # nested in finish
                ["repro.sim.replay", 75, 5]])          # and in itself
    t = spanlib.self_times(run)
    assert t["repro.sim.device_call"] == pytest.approx(30e-9)
    assert t["repro.sim.finish"] == pytest.approx(10e-9)
    assert t["repro.sim.replay"] == pytest.approx(10e-9)
    assert spanlib.host_finish(run) == pytest.approx(10.0)
    assert spanlib.host_device_call(run) == pytest.approx(30.0)
    assert spanlib.host_flow(run) == pytest.approx(20.0)
    # the shares of every layer never sum past the spans' union
    assert sum(t.values()) == pytest.approx(70e-9)


def test_spans_are_clipped_to_the_window():
    run = _run([["repro.flow.codegen", 0, 30],         # opens before
                ["repro.flow.partition:dp", 15, 5],
                ["repro.sim.replay", 100, 50],         # runs past the end
                ["repro.sim.finish", 120, 5]])         # after the window
    assert spanlib.host_flow(run) == pytest.approx(20.0)
    assert spanlib.host_replay(run) == pytest.approx(10.0)
    assert spanlib.host_finish(run) is None
    assert spanlib.per_request(run, "repro.flow.codegen") is None
    assert spanlib.codegen_per_request(_run(
        [["repro.flow.codegen", 20, 5], ["repro.flow.codegen", 60, 5],
         ["repro.flow.codegen", 90, 5]], requests=2)) == pytest.approx(1.5)


def test_idle_unattributed_against_hand_computed_intervals():
    run = _run([["repro.sim.device_call", 20, 30],
                ["np.asarray(jax.Array)", 45, 20],
                ["repro.sim.replay", 80, 10]],
               modules=[["jit_stage_pass(1)", 25, 10],
                        ["jit_stage_pass(1)", 55, 10],
                        ["jit_stage_pass(1)", 105, 20]])
    # covered: spans [20, 50) + [80, 90), device [55, 65) + [105, 110):
    # 55 ns of the window; idle with nothing open: 45 ns
    assert spanlib.idle_unattributed(run) == pytest.approx(45.0)


def test_a_program_without_spans_reads_nothing():
    run = _run([["np.asarray(jax.Array)", 45, 20]],
               modules=[["jit_stage_pass(1)", 25, 10]])
    metrics = os.path.join(registry.BENCH_DIR, "metrics")
    readers = [registry.load_module(os.path.join(metrics, f)).read
               for f in sorted(os.listdir(metrics)) if f.endswith(".py")]
    span_readers = [r for r in readers if r.__module__ == "spanlib"]
    assert len(span_readers) == 17
    assert all(r(run) is None for r in span_readers)


def _span_metrics(mix):
    """The benchmark's per-layer metrics that read spans, in the cells
    of ``mix``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"] for w in spec["workloads"] if w["traffic"] == mix}
    out = []
    for m in spec["per_layer"]:
        if not cells & set(m.get("workloads", ())):
            continue
        mod = registry.load_module(os.path.join(
            registry.BENCH_DIR, "metrics", m["name"] + ".py"))
        if mod.read.__module__ == "spanlib":
            out.append(m)
    return out


# the spans one request of each mix opens
SPANS = {"sweep64": {"repro.explore.fleet",
                     "repro.flow.condense", "repro.flow.partition:dp",
                     "repro.flow.codegen", "repro.sim.decode_prep",
                     "repro.sim.device_call", "repro.sim.finish",
                     "repro.sim.replay"},
         "point": {"repro.sim.decode_prep", "repro.sim.device_call",
                   "repro.sim.finish", "repro.sim.replay"},
         "validate": {"repro.ref.forward", "repro.kernel.cim_mvm",
                      "repro.ref.oracle"}}


@pytest.mark.parametrize("mix", sorted(TINY_CELLS))
def test_traced_tiny_cell_reads_every_span_metric(tiny, mix):
    root, run = tiny
    cell = f"{TINY}.{mix}"
    metrics = _span_metrics(mix)
    assert metrics
    # the tiny cell reports this mix's span metrics, as a full-size one
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["per_layer"] = [dict(m, workloads=[cell]) for m in metrics]
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    out = run(cell, seconds=0.0, trace=True)
    assert out["correct"], out["checks"]
    for m in metrics:
        v = out["metrics"].get(m["name"], {}).get("value")
        assert isinstance(v, float) and v >= 0, (m["name"], v)
    if mix == "sweep64":
        # the fleet compiles its pinned program once per request
        assert out["metrics"]["codegen_per_request.sweep"]["value"] == 1.0
    path = os.path.join(root, "bench_out", "trace", cell + ".events.json.gz")
    with gzip.open(path, "rt") as f:
        red = tracereduce.Reduction(json.load(f))
    names = {n for n, s, d in red.host
             if n.startswith("repro.") and red.t0 <= s <= red.t1}
    assert names == SPANS[mix]
