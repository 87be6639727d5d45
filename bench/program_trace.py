"""What the program writes into a traced run's profile: its host spans
(`serve.*`, serving/engine.py) with their integer arguments, and the
name scopes of its compiled programs (`attn`, `mlp`, `paged_view`,
models/), read back as device self time per scope.

This is a second reading of the `.xplane.pb` that `bench/trace.py`
reduces. A TPU trace's "XLA Ops" events carry only the HLO instruction
(`%while.102 = ...`), no name stack; the stack is the instruction's
`op_name` metadata, found in the optimized HLO of each program that the
profiler keeps in its `/host:metadata` plane ("Hlo Proto"). That plane
is read from the file's protobuf encoding directly (`fields` below), one
instruction at a time, and each distinct op is resolved once.

  spans   host spans whose name starts with `serve.`, as (name, start,
          end, {arg: int}), inside the window
  self    {(program, op): device seconds} of the window: each "XLA Ops"
          event's duration minus what the events nested in it cover (a
          `while` holds its body's ops), so a loop is never counted
          twice
  calls   {program: runs} of the window ("XLA Modules")
  hlo     {program: where its HLO lies in the profile}, by the name
          its runs have in "XLA Modules"; `stacks_for` reads a
          program's {op: op_name} from it when first asked

The window is the reduced trace's (`red["t0"]`, `red["t1"]`: the
`bench.step` spans), so both readings cover the same steps. A profile
is read only if its `bench.step` spans are `red["steps"]` exactly, so a
stale profile, or another run's, is never read against this one.
"""
from __future__ import annotations

import glob
import os

from bench import trace as tr

SPAN_PREFIX = "serve."
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")


# ---------------- protobuf wire format ----------------

def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def fields(buf, start: int = 0, end: int | None = None):
    """(field number, value) of each field of the message in
    buf[start:end]: an int for a varint, a (start, end) pair for a
    length-delimited field, None for a fixed-width one."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _sub(buf, span, num: int):
    """The length-delimited fields numbered `num` of the message at
    `span`."""
    return [v for f, v in fields(buf, *span) if f == num]


def hlo_op_names(buf, span) -> dict:
    """{instruction name: op_name} of an HloProto (xla/service/hlo.proto:
    hlo_module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for mod in _sub(buf, span, 1):
        for comp in _sub(buf, mod, 3):
            for ins in _sub(buf, comp, 2):
                name, op = None, ""
                for f, v in fields(buf, *ins):
                    if f == 1:
                        name = _text(buf, v)
                    elif f == 7:
                        op = "".join(_text(buf, s) for s in _sub(buf, v, 2))
                if name is not None:
                    out[name] = op
    return out


def program_hlo(buf) -> dict:
    """{program: where its HloProto lies in buf}, for every program in an
    XSpace's metadata plane (tsl/profiler/protobuf/xplane.proto:
    planes 1 > name 2, event_metadata 4 (map: value 2 > name 2, stats 5),
    stat_metadata 5 (map: value 2 > id 1, name 2); XStat metadata_id 1,
    bytes_value 6)."""
    out = {}
    for plane in (v for f, v in fields(buf) if f == 1):
        parts = list(fields(buf, *plane))
        names = [_text(buf, v) for f, v in parts if f == 2]
        if names != [METADATA_PLANE]:
            continue
        hlo_ids = set()
        for f, v in parts:
            if f == 5:
                for meta in _sub(buf, v, 2):
                    kv = dict(fields(buf, *meta))
                    if 2 in kv and _text(buf, kv[2]) == HLO_STAT:
                        hlo_ids.add(kv.get(1, 0))
        for f, v in parts:
            if f != 4:
                continue
            for meta in _sub(buf, v, 2):
                name, protos = None, []
                for g, w in fields(buf, *meta):
                    if g == 2:
                        name = _text(buf, w)
                    elif g == 5:
                        st = dict(fields(buf, *w))
                        if st.get(1, 0) in hlo_ids and 6 in st:
                            protos.append(st[6])
                if name is not None and protos:
                    out[name] = protos[0]
    return out


# ---------------- device self time ----------------

def self_times(events, t0: float, t1: float, programs) -> tuple:
    """({(program, op): seconds}, {program: runs}) of one device.

    `events`: (op, start, end) of its "XLA Ops" line, in start order,
    nested events after their parent; `programs`: (program, start, end)
    of its "XLA Modules" line. An op counts under the program whose run
    holds its start, if that run starts in [t0, t1); its self time is
    its length minus that of the events nested directly in it."""
    runs = sorted((s, e, n) for n, s, e in programs if t0 <= s < t1)
    calls: dict = {}
    for _, _, n in runs:
        calls[n] = calls.get(n, 0) + 1
    out: dict = {}
    stack: list = []                    # [key, end, own length, nested]

    def close(item):
        if item[0] is not None:
            out[item[0]] = out.get(item[0], 0.0) + item[2] - item[3]

    k = 0
    for op, s, e in events:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        while k < len(runs) and runs[k][1] <= s:
            k += 1
        key = None
        if k < len(runs) and runs[k][0] <= s:
            key = (runs[k][2], op)
        stack.append([key, e, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out, calls


def op_of(event_name: str) -> str:
    """The HLO instruction an "XLA Ops" event runs: `%while.102 = ...`
    gives `while.102`."""
    return event_name.split(" ", 1)[0].lstrip("%")


# ---------------- loading ----------------

def load(path: str, t0: float, t1: float, steps=None) -> dict | None:
    """The reading described at the top of this module, of the profile
    at `path` over [t0, t1]; None where `steps` is given and the
    profile's `bench.step` spans, as (start, end) on bench/trace.py's
    clock, are not those."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    planes = list(pd.planes)
    spans, marks = [], []
    for plane in planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.step":
                    marks.append((ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if e > t0 and s < t1:
                    spans.append((ev.name, s, e, {
                        k: v for k, v in ev.stats if isinstance(v, int)}))
    if steps is not None and sorted(marks) != sorted(steps):
        return None
    self_s, calls = {}, {}
    for plane in planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        ops, programs = [], []
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                names: dict = {}
                for ev in line.events:
                    n = ev.name
                    op = names.get(n)
                    if op is None:
                        op = names[n] = op_of(n)
                    s = ev.start_ns * 1e-9
                    ops.append((op, s, s + ev.duration_ns * 1e-9))
            elif line.name == tr.MODULES_LINE:
                programs = [(ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events]
        ops.sort(key=lambda x: (x[1], -x[2]))
        times, runs = self_times(ops, t0, t1, programs)
        for key, sec in times.items():
            self_s[key] = self_s.get(key, 0.0) + sec
        for n, c in runs.items():
            calls[n] = calls.get(n, 0) + c
    spans.sort(key=lambda x: x[1])
    buf = memoryview(raw)
    return {"spans": spans, "self": self_s, "calls": calls, "buf": buf,
            "hlo": program_hlo(buf), "stacks": {}}


def profiles(root: str = TRACE_ROOT) -> list:
    """The profiles under `root`, newest first."""
    paths = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return sorted(paths, key=os.path.getmtime, reverse=True)


def of(red: dict, paths=None) -> dict | None:
    """The reading of the profile that `red` (bench/trace.py) reduces:
    the first of `paths` (by default every profile under `.bench_trace/`,
    where bench/run.py writes the run's profile, newest first) whose
    `bench.step` spans are `red["steps"]`. It is kept in `red` so that
    every reader of the run shares one reading; None where no profile is
    the run's."""
    if "program" not in red:
        red["program"] = None
        for path in profiles() if paths is None else paths:
            prog = load(path, red["t0"], red["t1"], red["steps"])
            if prog is not None:
                red["program"] = prog
                break
    return red["program"]


# ---------------- what the readers ask ----------------

def scope_time(prog: dict, scope: str, program: str) -> tuple:
    """(runs, seconds) of the programs whose name holds `program`: their
    runs, and the device self time of their ops whose name stack has
    `scope` as one of its parts."""
    runs = sum(c for n, c in prog["calls"].items() if program in n)
    sec = sum(s for (name, op), s in prog["self"].items()
              if program in name
              and scope in stacks_for(prog, name).get(op, "").split("/"))
    return runs, sec


def stacks_for(prog: dict, program: str) -> dict:
    """{op: op_name} of `program` ("XLA Modules" name, e.g.
    `jit__decode_fn(123)`), from the HLO the profile keeps under the same
    name; read once per program."""
    if program not in prog["stacks"]:
        span = prog["hlo"].get(program)
        prog["stacks"][program] = {} if span is None else hlo_op_names(
            prog["buf"], span)
    return prog["stacks"][program]


def ms_per_run(ctx: dict, scope: str, program: str):
    """`scope_time` per run of the program, in ms; None where the trace
    holds no op of the scope (a program built without it)."""
    prog = of(ctx["trace"])
    if prog is None:
        return None
    runs, sec = scope_time(prog, scope, program)
    return 1e3 * sec / runs if runs and sec > 0 else None


def spans(ctx: dict, name: str) -> list:
    """(start, end, args) of the program's host spans named `name` in the
    traced window."""
    prog = of(ctx["trace"])
    if prog is None:
        return []
    return [(s, e, a) for n, s, e, a in prog["spans"] if n == name]
