"""Each traffic mix's entry runs the rest of a run on the repository's
``tiny_cnn`` (CPU, no look for a chip), and a run whose timed path is
broken underneath comes out not correct."""

import json

import numpy as np
import pytest

from conftest import TINY, TINY_CELLS

CELLS = [f"{TINY}.{mix}" for mix in TINY_CELLS]


@pytest.mark.parametrize("cell", CELLS)
def test_one_request_on_tiny_cnn(tiny, cell):
    _, run = tiny
    out = run(cell, seconds=0.0)
    assert out["correct"], out["checks"]
    assert (out["attempted"], out["failed"]) == (1, 0)
    mine = TINY_CELLS[cell.split(".")[1]][0]
    assert sorted(out["metrics"]) == sorted([mine, "setup_s"])
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["window_compiles"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 == c["limit"]
               for c in out["checks"].values())
    json.dumps(out)


def _add_to_latencies(monkeypatch):
    """Every vector and MVM latency the device pass returns, plus one."""
    from repro.core import jaxsim
    inner = jaxsim._call_exec

    def broken(fn, sc, cols, n):
        lat, res = inner(fn, sc, cols, n)
        return lat + 1, res
    monkeypatch.setattr(jaxsim, "_call_exec", broken)


def _half_fleet(monkeypatch):
    """The fleet decodes the first half of its machines and hands their
    results to the second half too."""
    from repro.core import jaxsim
    inner = jaxsim.FleetStageDecoder.decode_stage

    def broken(self, programs, prep=None):
        outs = inner(self, programs, prep)
        h = (len(outs) + 1) // 2
        return outs[:h] + outs[:len(outs) - h]
    monkeypatch.setattr(jaxsim.FleetStageDecoder, "decode_stage", broken)


def _stale_sweep(monkeypatch):
    """Every sweep after the first returns the first one's records."""
    from repro.explore import ExplorationEngine
    inner = ExplorationEngine.evaluate
    first = []

    def broken(self, points, fidelity=None):
        if not first:
            first.append(inner(self, points, fidelity))
        return first[0]
    monkeypatch.setattr(ExplorationEngine, "evaluate", broken)


def _stale_point(monkeypatch):
    """Every simulated point after the first returns the first report."""
    from repro.core.simulator import Simulator
    inner = Simulator.run_model
    first = []

    def broken(self, model, gmem_image=None):
        if not first:
            first.append(inner(self, model, gmem_image))
        return first[0]
    monkeypatch.setattr(Simulator, "run_model", broken)


def _requant_off_by_one(monkeypatch):
    """The graph walk that feeds both the Pallas path and the system's
    own oracle rounds every requantized value one step up."""
    from repro.core import ref
    inner = ref.quantize

    def broken(acc, q, div=1):
        return np.clip(inner(acc, q, div).astype(np.int16) + 1, -128,
                       127).astype(np.int8)
    monkeypatch.setattr(ref, "quantize", broken)


def _kernel_element(monkeypatch):
    """The Pallas path's matmul returns one element off by one."""
    from repro.flow import backends
    inner = backends._pallas_matmul

    def broken(a, b):
        out = np.array(inner(a, b))
        out.flat[0] += 1
        return out
    monkeypatch.setattr(backends, "_pallas_matmul", broken)


FAULTS = [
    (f"{TINY}.sweep64", _add_to_latencies),
    (f"{TINY}.sweep64", _half_fleet),
    (f"{TINY}.sweep64", _stale_sweep),
    (f"{TINY}.point", _add_to_latencies),
    (f"{TINY}.point", _stale_point),
    (f"{TINY}.validate", _requant_off_by_one),
    (f"{TINY}.validate", _kernel_element),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c.split('.')[-1]}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, cell, fault):
    _, run = tiny
    fault(monkeypatch)
    try:
        out = run(cell, seconds=1.0)
    except AssertionError as e:
        # the system's own oracle check stops the run at its warm-up
        # request: it exits non-zero with no result line
        assert "func:pallas mismatch" in str(e)
        return
    assert out["attempted"] >= 2
    assert not out["correct"], out["checks"]
