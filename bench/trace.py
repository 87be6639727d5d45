"""Reduction of a profiler trace to busy intervals, device time by name,
host spans and idle gaps.

`reduce(path, t0_ns, t1_ns)` reads an `.xplane.pb` with
`jax.profiler.ProfileData` and keeps what lies inside [t0_ns, t1_ns]:

  busy      union of the intervals in which an operation ran on each
            device ("XLA Ops" lines of the `/device:TPU:n` planes),
            clipped to the window; busy_s is its length averaged over
            the devices
  ops       device seconds by operation name ("XLA Ops")
  modules   device seconds and calls by program name ("XLA Modules":
            the jitted functions, e.g. `jit__decode_fn`)
  spans     host spans of the harness (`bench.*`) as (name, start, end)
  gaps      idle gaps between busy intervals, longest first, each
            labelled by the host span it falls in

The pure functions below take plain lists so that they can be checked on
a small synthetic trace.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def union(intervals):
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def covered(intervals, t0, t1) -> float:
    """Length of the part of [t0, t1] that disjoint `intervals` cover."""
    return sum(e - s for s, e in clip(intervals, t0, t1))


def idle_gaps(busy, spans, t0, t1, top: int = 10):
    """The longest idle stretches of [t0, t1] between disjoint busy
    intervals, each labelled by the host span that covers most of it
    ('none' where no span does)."""
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, name = 0.0, "none"
        for n, a, b in spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, n
        out.append((name, e - s))
    return out


def by_name(events, t0, t1):
    """{name: [calls, seconds]} of (name, start, end) events starting in
    [t0, t1)."""
    out: dict = {}
    for n, s, e in events:
        if t0 <= s < t1:
            c = out.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += e - s
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    """(device op events per device, module events, host spans), each
    event as (name, start_s, end_s) on the profiler's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in line.events]
                if line.name == OPS_LINE:
                    ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules += evs
        else:
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9)
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    return ops, modules, spans


def reduce(ops: dict, modules: list, spans: list) -> dict:
    """Everything the readers need, over the window from the first
    `bench.step` span's start to the last one's end."""
    steps = sorted((s, e) for n, s, e in spans if n == "bench.step")
    if not steps or not ops:
        raise ValueError("the trace holds no step span or no device ops")
    t0, t1 = steps[0][0], steps[-1][1]
    busy = {d: union((s, e) for _, s, e in evs) for d, evs in ops.items()}
    busy_s = sum(covered(b, t0, t1) for b in busy.values()) / len(busy)
    first = sorted(busy)[0]
    all_ops: dict = {}
    for evs in ops.values():
        for n, (c, sec) in by_name(evs, t0, t1).items():
            a = all_ops.setdefault(n, [0, 0.0])
            a[0] += c
            a[1] += sec
    return {"t0": t0, "t1": t1, "window_s": t1 - t0, "busy_s": busy_s,
            "n_devices": len(busy), "busy": busy[first],
            "ops": all_ops, "modules": by_name(modules, t0, t1),
            "steps": steps,
            "spans": [x for x in spans if x[2] > t0 and x[1] < t1],
            "gaps": idle_gaps(clip(busy[first], t0, t1), spans, t0, t1)}


def module_time(red: dict, fragment: str) -> tuple:
    """(calls, seconds) of the programs whose name holds `fragment`."""
    calls, sec = 0, 0.0
    for n, (c, s) in red["modules"].items():
        if fragment in n:
            calls, sec = calls + c, sec + s
    return calls, sec


def op_time(red: dict, fragment: str) -> tuple:
    """(calls, seconds) of the device operations whose name holds
    `fragment`."""
    calls, sec = 0, 0.0
    for n, (c, s) in red["ops"].items():
        if fragment in n:
            calls, sec = calls + c, sec + s
    return calls, sec


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[n, s] for n, (_, s) in ops],
            "idle_gaps": [[n, s] for n, s in red["gaps"][:top]]}
