#!/usr/bin/env python3
"""Readings that set a cell's limits: sound runs and the control.

    python3 bench/control.py --workload resnet18-224.sweep64 \\
        --seeds 11,12,13 --seconds 10

For each seed, in one process on the chip: the cell's set-up, a window
of ``--seconds`` at the cell's own load, then two readings of every
compared number, each against the plain reference:

* ``sound`` -- what the window produced, as a benchmark run checks it;
* ``control`` -- the reference itself computed one precision below what
  the configuration states (float32 statistics for the simulate cells,
  int4 operands for the validation cell), put in the system's place.

A line per seed, then the largest sound reading and the smallest
control reading of each number.  The benchmark's own runs never run
this; the limits in ``traffic/*.json`` are set between the two.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import harness
    import registry
    import traffic

    cell = registry.Cell(ROOT, args.workload)
    try:
        device = harness.device_info(cell.chips)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        rngs = traffic.streams(seed)
        entry = cell.entry_module().Entry(cell, rngs)
        entry.setup()
        run = harness.Run(cell, args.seconds, False)
        harness.run_window(run, entry, rngs)
        done = [r for r in run.requests if r.ok]
        sound = {c["name"]: c["value"]
                 for c in entry.check(done, traffic.streams(seed)["check"])}
        ctl = {c["name"]: c["value"] for c in entry.check(
            done, traffic.streams(seed)["check"], control=True)}
        for k, v in sound.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in ctl.items():
            upper[k] = min(upper.get(k, v), v)
        print(json.dumps({"seed": seed, "requests": len(run.requests),
                          "failed": len(run.requests) - len(done),
                          "sound": sound, "control": ctl}), flush=True)
    print(json.dumps({"workload": cell.name, "device": device,
                      "lower": lower, "upper": upper,
                      "wall_s": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
