#!/usr/bin/env python3
"""Readings that set a cell's correctness limit and bounds, on the chip.

    python bench/calibrate.py --workload <name> --seeds 11,12,13 \\
        --seconds 51 [--control] [--mode olm16t10]

Runs the cell once per seed in one process (set-up, a window of
`--seconds`, the reference on a sample of what was served), and prints
one JSON line per seed with the cell's end-to-end metrics, `gap_max`
and, with `--control`, the gap of the tokens that the traffic file's
`check.control` (the reference in a lower precision) puts first on the
same positions. `--mode` serves the cell with another of the program's
numerics modes in place of the traffic file's, as a control of the
program's own. The limit in the traffic file lies above the largest
program reading and below the smallest control reading (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--mode")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import run, spec
    from repro.launch.compile_cache import enable_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return run.EXIT_NO_CHIP
    cell = spec.cell(spec.benchmark(), args.workload)
    if args.mode:
        cell["traffic"] = {**cell["traffic"], "mode": args.mode}
    peaks = spec.peaks(devices[0].device_kind)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, peaks,
                           time.monotonic(), control=args.control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mode": cell["traffic"]["mode"],
                          "correct": res["correct"],
                          "gap_max": res["checks"]["gap_max"]["value"],
                          "control": res.get("control"),
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": res["metrics"],
                          "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
