"""The lower-precision controls: the reference computed one precision
below what the configuration states, put in the system's place, has to
come out not correct."""

import json
import os
import warnings

import numpy as np

import registry
import traffic
from conftest import ROOT, TINY


class _Req:
    def __init__(self, payload, result):
        self.payload = payload
        self.result = result


def _entry(cell_name, root=ROOT):
    cell = registry.Cell(root, cell_name)
    return cell, cell.entry_module().Entry(cell, traffic.streams(2**31 + 3))


def test_float32_statistics_are_rejected_at_resnet18_224():
    """The simulated statistics in float32 in place of float64: at the
    cell's size the ledger's sums round (its largest count, local-memory
    bytes, passes 2^24), and the energy comparison sees it."""
    from repro import flow
    from repro.core.arch import default_chip
    from repro.core.mapping import CostParams
    from repro.flow import CompileOptions

    cell, entry = _entry("resnet18-224.sweep64")
    cfg = cell.config
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        entry.cm = flow.compile(cfg["model"], default_chip(), CompileOptions(
            strategy=cfg["strategy"], params=CostParams(batch=cfg["batch"]),
            workload_kw=cfg["workload_kw"],
            fidelity="simulate")).ensure_model()
    entry.mix = dict(entry.mix, points_per_request=1, check_sample=1)
    pts = traffic.draw_points(entry.mix["draws"], 1,
                              np.random.default_rng(5))
    req = _Req({"points": pts}, None)
    checks = {c["name"]: c for c in entry.check(
        [req], np.random.default_rng(0), control=True)}
    assert checks["energy_gap"]["value"] > checks["energy_gap"]["limit"]


def test_int4_operands_are_rejected(tiny):
    root, _ = tiny
    cell, entry = _entry(f"{TINY}.validate", root)
    entry.setup()
    req = _Req({"image_seeds": [11]}, None)
    checks = entry.check([req], np.random.default_rng(0), control=True)
    assert checks[0]["value"] > checks[0]["limit"] == 0


def test_resnet18_classifier_sees_a_signal():
    """The pool fused into the last block applies its convolution's
    shift twice; sized on the pooled vector, that shift leaves the
    classifier (the only M=1 kernel call) a real input, so the check of
    its output tests more than its bias."""
    cell = registry.Cell(ROOT, "resnet18-224.validate")
    ref, cfg = cell.reference_module(), cell.config
    rng = np.random.default_rng(2**31 + 5)
    p = ref.make_params(cfg, rng)
    shifts = ref.calibrate(cfg, p, ref.make_image(cfg, rng))
    t = ref.forward(cfg, p, shifts, ref.make_image(cfg, rng))
    assert np.count_nonzero(t["avgpool"]) > t["avgpool"].size // 2
    bias_only = ref.refcnn.requant(p["fc.bias"], shifts["fc"])
    assert np.count_nonzero(t["fc"] != bias_only) > t["fc"].size // 2
