"""Shared set-up of the benchmark's CPU tests: the harness's modules on
the import path, and a throw-away checkout whose cells run the
repository's ``tiny_cnn`` graph through each traffic mix instead of
the full-size configurations."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "tiny_cnn-8"


# the tiny checkout's cells: one per kind of request, and the end-to-end
# metric each reports
TINY_CELLS = {"sweep64": ("points_per_s", "points/s", "higher"),
              "point": ("point_p90_ms", "ms", "lower"),
              "validate": ("validated_macs_per_s", "MAC/s", "higher")}


def make_checkout(tmp: str) -> str:
    """A checkout whose ``BENCHMARK.json`` runs each traffic mix of the
    benchmark (cells ``tiny_cnn-8.<mix>``) on the tiny configuration,
    with the harness's code dirs linked in; returns its ``bench``
    directory."""
    bench = os.path.join(tmp, "bench")
    os.makedirs(os.path.join(bench, "configs"))
    for d in ("entries", "e2e", "metrics", "traffic"):
        os.symlink(os.path.join(BENCH, d), os.path.join(bench, d))
    for f in (TINY + ".json", TINY + ".py"):
        shutil.copy(os.path.join(DATA, f), os.path.join(bench, "configs", f))
    cells = [f"{TINY}.{mix}" for mix in TINY_CELLS]
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": TINY, "source": "repository test graph",
                     "file": f"bench/configs/{TINY}.json", "reduced": [],
                     "why": "CPU tests"}],
        "workloads": [{"name": c, "config": TINY, "traffic": c.split(".")[1],
                       "chips": 1, "why": "CPU tests"} for c in cells],
        "end_to_end": [
            {"name": m, "unit": u, "better": b, "bound": 0.05,
             "source": "host_clock", "workloads": [f"{TINY}.{mix}"]}
            for mix, (m, u, b) in TINY_CELLS.items()] + [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": f"device_idle.{mix}", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "device", "moves": m,
             "workloads": [f"{TINY}.{mix}"]}
            for mix, (m, _, _) in TINY_CELLS.items()],
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return bench


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """``(root, run)``: ``run(cell, seed, seconds)`` runs one cell of the
    tiny checkout in this process, skipping only the look for a chip;
    the process-wide settings a run changes (JAX's cache threshold, the
    flow pass cache a sweep attaches) are put back after."""
    import jax

    import harness
    import registry
    from repro import flow
    from repro.flow.diskcache import ENV_VAR

    bench = make_checkout(str(tmp_path))
    init = registry.Cell.__init__

    def cell_init(self, root, name, bench_dir=registry.BENCH_DIR):
        init(self, root, name, bench)
    monkeypatch.setattr(registry.Cell, "__init__", cell_init)
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    disk = flow.default_pipeline().disk
    env = os.environ.get(ENV_VAR)

    def run(cell, seed=2**31 + 17, seconds=0.3, trace=False):
        import time
        return harness.run_cell(str(tmp_path), cell, seed, seconds, trace,
                                time.perf_counter(), check_device=False)
    yield str(tmp_path), run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
    flow.default_pipeline().disk = disk
    if env is None:
        os.environ.pop(ENV_VAR, None)
    else:
        os.environ[ENV_VAR] = env
