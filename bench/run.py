#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload resnet18-224.sweep64 --seed 7 \\
        --seconds 10 --trace 0

Reads ``BENCHMARK.json`` at the checkout's root and the cell's files
under ``bench/`` (see ``bench/registry.py``), drives the system under
test in ``src/`` on the TPU this process finds, and prints one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiler trace of the
window), ``device`` and, last, ``checks``: every number compared with
the reference beside its limit (also the last lines of standard
error).  Without a TPU, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))
# libtpu writes its logs to a fixed directory under /tmp unless told
# otherwise; a run keeps everything it writes inside its checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench_out",
                                                  "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    try:
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
