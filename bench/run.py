#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print one result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell (BENCHMARK.json `workloads`) names
a configuration file and a traffic file; the traffic file's `kind` names
the loop driver in `bench/drivers/`. Set-up builds the model's weights
from the seed on the device, builds the serving engine and compiles
every program the traffic can reach (from the persistent compile cache
after a checkout's first run). The window then serves the traffic for
`--seconds`, closing at the end of the first engine step that ends
after it. With `--trace 1` the loop goes on for the traffic file's
`trace_steps` steps under the profiler, and the per-layer metrics are
read from that trace (`bench/metrics/`) instead of the end-to-end ones.

Then the program's state is freed and a sample of the served requests is
compared with the plain reference (`bench/correct.py`). Standard error
ends with each compared number beside its limit; the last line of
standard output is the JSON result. With no TPU, or fewer chips than the
cell asks for, or no program next to `bench/`, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GOOD_FINISH = ("length", "eos", "max_len")
EXIT_NO_PROGRAM, EXIT_NO_CHIP = 2, 3


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileCounter:
    """Counts the backend compiles JAX reports while it is entered, to
    show that none happens inside the window."""

    def __init__(self):
        self.n = 0

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def end_to_end(cell, everyone, win, setup_s) -> dict:
    """The cell's end-to-end metrics: tokens and inter-token gaps of every
    request inside the window."""
    from bench import stats
    o, c = win["open"], win["close"]
    values = {"setup_s": lambda: setup_s,
              "out_tok_s": lambda: stats.tokens_in(everyone, o, c) / (c - o),
              "itl_p95_ms": lambda: 1e3 * stats.percentile(
                  stats.gaps(everyone, o, c), 95)}
    return {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]}
            for m in cell["end_to_end"]}


def per_layer(cell, ctx) -> dict:
    from bench import spec
    out = {}
    for m in cell["per_layer"]:
        stem, _, variant = m["name"].partition(".")
        v = spec.reader(m["name"]).read({**ctx, "variant": variant})
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             peaks: dict, t_start: float, control: bool = False) -> dict:
    """Everything after the chip check; returns the result object. With
    `control` (bench/calibrate.py) the readings, with those of the
    traffic file's `check.control` on the same sample, are added under
    "control"."""
    import jax

    from bench import correct, spec, traffic as gen
    from bench.session import Session
    from bench import trace as tr

    config, traffic = cell["config"], cell["traffic"]
    m = config["model"]
    sess = Session(config, traffic, seed)
    count = traffic.get("requests", 0) or int(math.ceil(
        traffic["rate"] * (traffic.get("warm_s", 0) + seconds + 180))) + 64
    reqs = gen.requests(traffic, m["vocab_size"], seed, count)
    drv = spec.driver(traffic["kind"])
    st = drv.setup(sess, traffic, reqs)
    traces0 = (sess.engine.prefill_traces, sess.engine.decode_traces)
    with CompileCounter() as compiles:
        win = drv.window(sess, st, seconds)
    setup_s = win["open"] - t_start
    in_window = compiles.n
    traced = (sess.engine.prefill_traces - traces0[0],
              sess.engine.decode_traces - traces0[1])
    drv.settle(sess, st, win["close"])
    red = None
    if trace:
        log_dir = os.path.join(ROOT, ".bench_trace", cell["workload"]["name"])
        shutil.rmtree(log_dir, ignore_errors=True)
        first = len(sess.steps)
        jax.profiler.start_trace(log_dir)
        try:
            drv.more(sess, st, traffic["trace_steps"],
                     traffic.get("trace_prefill", False))
        finally:
            jax.profiler.stop_trace()
        red = tr.reduce(*tr.load(tr.find_xplane(log_dir)))
        red["host_steps"] = sess.steps[first:]
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    o, c = win["open"], win["close"]
    everyone = [r for r in sess.reqs.values() if r["rid"] >= 0]
    due_in = [r for r in everyone if o <= r["due"] <= c]
    recs = [r for r in everyone if o <= r["due"] <= c
            or any(o < t <= c for t in r["token_times"])]
    attempted = len(recs)
    failed = sum(1 for r in recs if not r["token_times"] or (
        r["req"].finish_reason is not None
        and r["req"].finish_reason not in GOOD_FINISH))
    late = [r["submitted"] - r["due"] for r in due_in]
    window_steps = [s for s in sess.steps if s["t0"] >= o and s["t1"] <= c]
    if trace:
        metrics = per_layer(cell, {
            "trace": red, "window_steps": window_steps, "open": o,
            "close": c, "model": m, "traffic": traffic, "peaks": peaks})
    else:
        metrics = end_to_end(cell, everyone, win, setup_s)
    picked = [{"rid": r["rid"], "req": r["req"]} for r in correct.sample(
        recs, seed, traffic["check"]["sample"])]

    # free the program's state (and its compiled programs) before the
    # reference runs, and say how much the device still holds
    sess.drop()
    del sess, st, everyone, due_in, recs
    gc.collect()
    jax.clear_caches()
    held = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    print(f"device bytes in use before the reference: {held}",
          file=sys.stderr)
    read = correct.readings(spec.reference(config["reference"]), seed, m,
                            picked, traffic["check"]["control"] if control
                            else None)
    limit = traffic["check"]["gap_max"]
    ok = read["gap_max"] <= limit

    from bench import stats
    if traffic["kind"] == "open" and late:
        print("generator lateness s: p50 %.6f p95 %.6f max %.6f" % (
            stats.percentile(late, 50), stats.percentile(late, 95),
            max(late)), file=sys.stderr)
    print(f"window {c - o:.3f} s, {len(window_steps)} steps, compiles in "
          f"window {in_window}, new traces {traced}; compared "
          f"{read['tokens']} served tokens of {len(picked)} requests, "
          f"{read['mismatch']} not the reference's first",
          file=sys.stderr)
    print(f"check gap_max {read['gap_max']!r} limit {limit!r}",
          file=sys.stderr)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["workload"]["chips"],
              "memory_peak_bytes": int(mem)}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = tr.breakdown(red)
    if control:
        result["control"] = read
    result["checks"] = {"gap_max": {"value": read["gap_max"],
                                    "limit": limit}}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not next to bench/",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import spec
    cell = spec.cell(spec.benchmark(), args.workload)
    import jax
    devices = jax.devices()
    chips = cell["workload"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      spec.peaks(devices[0].device_kind), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
