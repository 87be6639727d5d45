#!/usr/bin/env python3
"""One-time sweep for the knee of an open-loop cell, on the chip.

    python bench/sweep.py --workload internlm2-native-chat \\
        --rates 0.3,0.36,0.42,0.48 --warm 60 --seconds 150 --seed 7

One process, one set-up; then, for each offered rate in rising order,
`--warm` seconds of the cell's traffic at that rate (arrivals go on from
the engine's state at the previous rate, so the window opens near that
rate's occupancy) and a window of `--seconds`, which should span two or
more request lifetimes. Prints one JSON line per rate: the requests due
in the window and finished, the queue waiting for a slot at each third
of the window (a backlog that keeps growing means the rate is past the
knee), the lanes in use at the close, the TTFT and inter-token tails,
and failures. The knee is the highest rate with no growing backlog and
no failed request; the cell's rate is set at about four fifths of it,
by hand, in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import deque

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RID_STRIDE = 1_000_000      # rids of rate i start at (i + 1) * stride


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--warm", type=float, default=60.0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import run, spec, stats, traffic as gen
    from bench.drivers import open as drv
    from bench.session import Session
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return run.EXIT_NO_CHIP
    enable_compile_cache()
    cell = spec.cell(spec.benchmark(), args.workload)
    traffic, m = cell["traffic"], cell["config"]["model"]
    sess = Session(cell["config"], traffic, args.seed)
    drv.setup(sess, {**traffic, "warm_s": 0}, [])
    rates = sorted(float(r) for r in args.rates.split(","))
    for i, rate in enumerate(rates):
        count = int(rate * (args.warm + args.seconds + 60)) + 64
        reqs = gen.requests({**traffic, "rate": rate}, m["vocab_size"],
                            args.seed + i, count)
        for r in reqs:
            r["rid"] += (i + 1) * RID_STRIDE
        st = {"todo": deque(reqs), "t0": time.monotonic()}
        o = drv.run_until(sess, st, st["t0"] + args.warm)
        queue = []
        for k in (1, 2, 3):
            c = drv.run_until(sess, st, o + args.seconds * k / 3)
            queue.append(len(sess.engine.queue))
        lanes = len(sess.engine.active)
        everyone = list(sess.reqs.values())
        due = [r for r in everyone if o <= r["due"] <= c]
        line = {"rate": rate, "window_s": c - o, "due": len(due),
                "finished": sum(1 for r in due
                                if r["req"].finish_reason is not None),
                "failed": sum(1 for r in due if r["req"].finish_reason
                              not in (None,) + run.GOOD_FINISH),
                "queue_thirds": queue, "lanes_at_close": lanes,
                "ttft_p90_s": stats.percentile(
                    stats.ttfts(due, c), 90) if due else None,
                "itl_p95_ms": 1e3 * stats.percentile(
                    stats.gaps(everyone, o, c), 95),
                "out_tok_s": stats.tokens_in(everyone, o, c) / (c - o)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
