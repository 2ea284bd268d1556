"""The reduction from trace events to the per-layer numbers."""

import gzip
import json
import os

import pytest

import tracereduce
from conftest import DATA


def _events():
    # two devices' worth of programs in a 100 ns window; host annotations
    # and one call inside the request
    return {
        "devices": {"TPU:0": {
            "modules": [["jit_stage_pass(1)", 10, 20],
                        ["jit_stage_pass(1)", 20, 20],
                        ["jit_cim_mvm(2)", 70, 10],
                        ["jit_stage_pass(1)", 95, 10]],
            "ops": [["%fusion.1 = u32[8] fusion(u32[8])", 10, 15],
                    ["%cim_mvm.1 = s32[8,8] custom-call(s8[8,8])", 72, 6],
                    ["%copy = s8[8] copy(s8[8])", 78, 2],
                    ["%cim_mvm.1 = s32[8,8] custom-call(s8[8,8])", 85, 2]],
        }},
        "host": [["bench.window", 0, 100],
                 ["bench.request", 0, 100],
                 ["np.asarray(jax.Array)", 45, 10]],
    }


def test_busy_union_and_idle_share():
    r = tracereduce.Reduction(_events())
    # [10, 40) + [70, 80) + [95, 100) inside the window
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s() == pytest.approx(45e-9)
    assert r.idle_share() == pytest.approx(0.55)


def test_program_and_kernel_time_by_name():
    r = tracereduce.Reduction(_events())
    # the execution that runs past the window's end is left out
    assert [e[1] for e in r.module_events("jit_stage_pass")] == [10, 20]
    assert r.module_s("jit_stage_pass") == pytest.approx(40e-9)
    # a custom-call outside every jit_cim_mvm execution is not the kernel
    assert r.kernel_s("jit_cim_mvm") == pytest.approx(6e-9)


def test_longest_gaps_carry_the_innermost_host_event():
    r = tracereduce.Reduction(_events())
    gaps = r.idle_gaps(10)
    assert gaps[0] == ["np.asarray(jax.Array)", pytest.approx(30e-9)]
    assert [g[0] for g in gaps[1:]] == ["bench.request"] * 2
    assert sorted(g[1] for g in gaps[1:]) == [pytest.approx(10e-9),
                                             pytest.approx(15e-9)]


def test_recorded_validation_trace():
    """The events of a traced run of resnet18-224.validate on a v5e
    chip: three func:pallas requests at resnet18@224, each 21 kernel
    calls in 21 jit_cim_mvm executions."""
    with gzip.open(os.path.join(DATA, "validate_trace.json.gz"), "rt") as f:
        ev = json.load(f)
    r = tracereduce.Reduction(ev)
    assert sum(e[0] == "bench.request" for e in ev["host"]) == 3
    kernels = r.kernel_events("jit_cim_mvm")
    assert len(kernels) == 63
    assert all("custom-call" in k[0] for k in kernels)
    mods = r.module_events("jit_cim_mvm")
    assert len(mods) == 63
    # every kernel lies inside its program, every program inside busy
    assert r.kernel_s("jit_cim_mvm") < r.module_s("jit_cim_mvm") <= r.busy_s()
    assert 0.99 < r.idle_share() < 1.0
    top = r.top_ops(3)
    assert top[0][0] == "cim_mvm.1"
    assert top[0][1] == pytest.approx(r.kernel_s("jit_cim_mvm"))
    # the three longest gaps are the numpy oracle's, one per request
    assert [g[0] for g in r.idle_gaps(3)] == ["bench.request"] * 3
    assert all(g[1] > 3.0 for g in r.idle_gaps(3))
