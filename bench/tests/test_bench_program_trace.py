"""The second reading of a traced run's profile (bench/program_trace.py):
device self time by name scope, the program's host spans, and the
readers built on them, on synthetic traces and on a profile taken here."""
import glob
import os

import pytest

from bench import program_trace as pt
from bench import trace as tr
from bench.metrics import (attention_ms, decode_step_ms, device_idle_pct,
                           host_ms_per_step, host_work_ms_per_step, mlp_ms,
                           olm_kernel_ms, paged_view_ms, prefill_ms_per_ktok)

DECODE = "jit__decode_fn(11)"
STACKS = {"while.102": "jit(_decode_fn)/while",
          "fusion.1": "jit(_decode_fn)/while/body/closed_call/attn/dot_general",
          "fusion.2": "jit(_decode_fn)/while/body/closed_call/mlp/dot_general",
          "while.104": "jit(_decode_fn)/while/body/closed_call/attn/"
                       "paged_view/while",
          "dynamic_slice.9": "jit(_decode_fn)/while/body/closed_call/attn/"
                             "paged_view/while/body/dynamic_slice",
          "olm_matmul_fused_pallas.3": "jit(_decode_fn)/jit(_olm_matmul_impl)/"
                                       "pallas_call"}


@pytest.fixture
def layer_scan():
    """One decode run of [0, 10]: a layer scan `while.102` holding an
    attention GEMM, the view's `while.104` (holding one slice) and an MLP
    GEMM, then the head; a run of another program; an op outside any."""
    ops = [("while.102", 0.0, 9.0),
           ("fusion.1", 1.0, 3.0),
           ("while.104", 3.0, 6.0),
           ("dynamic_slice.9", 4.0, 5.0),
           ("fusion.2", 6.5, 8.5),
           ("olm_matmul_fused_pallas.3", 9.0, 10.0),
           ("fusion.1", 11.0, 12.0),
           ("stray", 13.0, 14.0)]
    programs = [(DECODE, 0.0, 10.0), ("jit__chunk_fn(12)", 11.0, 12.0)]
    return ops, programs


def test_self_time_counts_a_loop_once(layer_scan):
    ops, programs = layer_scan
    self_s, calls = pt.self_times(ops, 0.0, 20.0, programs)
    assert calls == {DECODE: 1, "jit__chunk_fn(12)": 1}
    assert self_s[(DECODE, "while.102")] == pytest.approx(9 - 2 - 3 - 2)
    assert self_s[(DECODE, "while.104")] == pytest.approx(3 - 1)
    assert self_s[(DECODE, "dynamic_slice.9")] == pytest.approx(1)
    assert self_s[("jit__chunk_fn(12)", "fusion.1")] == pytest.approx(1)
    assert not any(op == "stray" for _, op in self_s)
    # self times partition the busy time of the runs
    assert sum(self_s.values()) == pytest.approx(10 + 1)


def test_runs_outside_the_window_are_left_out(layer_scan):
    ops, programs = layer_scan
    self_s, calls = pt.self_times(ops, 10.5, 20.0, programs)
    assert calls == {"jit__chunk_fn(12)": 1}
    assert list(self_s) == [("jit__chunk_fn(12)", "fusion.1")]


def _prog(layer_scan, spans=(), stacks=STACKS):
    ops, programs = layer_scan
    self_s, calls = pt.self_times(ops, 0.0, 20.0, programs)
    return {"spans": list(spans), "self": self_s, "calls": calls,
            "buf": None, "hlo": {}, "stacks": {DECODE: dict(stacks)}}


def test_scope_time_by_name_stack(layer_scan):
    prog = _prog(layer_scan)
    assert pt.scope_time(prog, "attn", "_decode_fn") == (
        1, pytest.approx(2 + 2 + 1))
    assert pt.scope_time(prog, "paged_view", "_decode_fn") == (
        1, pytest.approx(2 + 1))
    assert pt.scope_time(prog, "mlp", "_decode_fn") == (1, pytest.approx(2))
    # a scope is a whole part of the stack, not a fragment of one
    assert pt.scope_time(prog, "paged", "_decode_fn")[1] == 0
    # the chunk program's fusion.1 is not the decode program's
    assert pt.scope_time(prog, "attn", "_chunk_fn") == (1, 0.0)


def _ctx(prog, red=None):
    red = dict(red or {"t0": 0.0, "t1": 20.0})
    red["program"] = prog
    return {"trace": red, "variant": "chat"}


def test_scope_readers(layer_scan):
    ctx = _ctx(_prog(layer_scan))
    assert attention_ms.read(ctx) == pytest.approx(5e3)
    assert paged_view_ms.read(ctx) == pytest.approx(3e3)
    assert mlp_ms.read(ctx) == pytest.approx(2e3)


def test_span_readers():
    spans = [("serve.step", 0.0, 1.0, {"step_num": 0}),
             ("serve.schedule", 0.1, 0.3, {}),
             ("serve.prefill", 0.1, 0.3, {"rows": 3, "rows_computed": 4,
                                          "tokens": 300,
                                          "tokens_computed": 512}),
             ("serve.sync", 0.2, 0.3, {}),
             ("serve.decode", 0.3, 1.0, {"lanes": 3}),
             ("serve.sync", 0.4, 0.9, {}),
             ("serve.step", 1.0, 1.5, {"step_num": 1}),
             ("serve.chunk", 1.0, 1.2, {"tokens": 100,
                                        "tokens_computed": 256}),
             ("serve.sync", 1.1, 1.2, {}),
             ("serve.decode", 1.2, 1.5, {"lanes": 4}),
             ("serve.sync", 1.3, 1.4, {}),
             ("serve.step", 1.5, 1.9, {"step_num": 2}),
             ("serve.decode", 1.5, 1.9, {"lanes": 4}),
             ("serve.sync", 1.6, 1.8, {})]
    ctx = _ctx({"spans": spans, "self": {}, "calls": {}, "buf": None,
                "hlo": {}, "stacks": {}})
    # prefill work and syncs left out: step 0 1.0 - 0.2 (prefill, its
    # sync inside) - 0.5; step 1 0.5 - 0.2 (chunk) - 0.1; step 2 0.4 - 0.2
    assert host_work_ms_per_step.read(ctx) == pytest.approx(
        1e3 * (0.3 + 0.2 + 0.2) / 3)


def test_readers_read_nothing_from_a_program_without_spans_or_scopes(
        layer_scan):
    """What the readers give on a program built before the spans and
    scopes: no metric, and no error."""
    ctx = _ctx(_prog(layer_scan, stacks={
        k: "jit(_decode_fn)/while/body/closed_call/dot_general"
        for k in STACKS}))
    for reader in (attention_ms, paged_view_ms, mlp_ms,
                   host_work_ms_per_step):
        assert reader.read(ctx) is None
    for reader in (attention_ms, host_work_ms_per_step):
        assert reader.read(_ctx(None)) is None


def test_existing_readers_unchanged_by_the_second_reading(layer_scan):
    ops, programs = layer_scan
    spans = [("bench.step", 0.0, 10.2), ("bench.step", 10.2, 12.5)]
    red = tr.reduce({"/device:TPU:0": ops}, programs, spans)
    red["host_steps"] = [{"prompt_tokens": 0, "decode_tokens": 8},
                         {"prompt_tokens": 100, "decode_tokens": 0}]
    old = (decode_step_ms, device_idle_pct, host_ms_per_step, olm_kernel_ms,
           prefill_ms_per_ktok)
    ctx = {"trace": red, "variant": "chat"}
    before = [r.read(ctx) for r in old]
    breakdown = tr.breakdown(red)
    red["program"] = _prog(layer_scan, spans=[
        ("serve.step", 0.0, 10.1, {"step_num": 0}),
        ("serve.sync", 9.0, 10.0, {})])
    assert attention_ms.read(ctx) == pytest.approx(5e3)
    assert host_work_ms_per_step.read(ctx) == pytest.approx(1e3 * 9.1)
    assert [r.read(ctx) for r in old] == before
    assert tr.breakdown(red) == breakdown


def test_op_of_reads_the_instruction_of_an_event():
    assert pt.op_of("%while.102 = (s32[], bf16[8,1,2048]) while(...)") == \
        "while.102"
    assert pt.op_of("fusion.1") == "fusion.1"


def test_a_profile_taken_here(tmp_path):
    """The whole reading of a real profile: the program's HLO from the
    metadata plane with its name stacks, and host spans with their
    arguments (the CPU has no device plane, so no device time)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("attn"):
            with jax.named_scope("paged_view"):
                x = jax.lax.fori_loop(0, 3, lambda i, a: a * 1.5 + i, x)
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x.T)

    x = jnp.ones((8, 8))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("serve.step", step_num=7):
            step(x).block_until_ready()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    prog = pt.load(path, 0.0, float("inf"))
    assert [(n, a) for n, _, _, a in prog["spans"]] == [
        ("serve.step", {"step_num": 7})]
    name, = [n for n in prog["hlo"] if n.startswith("jit_step(")]
    stacks = pt.stacks_for(prog, name)
    parts = [set(op.split("/")) for op in stacks.values()]
    assert any({"attn", "paged_view", "while"} <= p for p in parts)
    assert any("mlp" in p and "attn" not in p for p in parts)
    assert pt.stacks_for(prog, "jit_step(0)") == {}


def _profile(log_dir, steps):
    """A profile taken here of `steps` harness steps, each one `bench.step`
    holding a `serve.step`; (path, its `bench.step` spans)."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(log_dir)):
        for i in range(steps):
            with jax.profiler.TraceAnnotation("bench.step"):
                with jax.profiler.TraceAnnotation("serve.step", step_num=i):
                    jnp.ones(4).block_until_ready()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    marks = sorted((s, e) for n, s, e in tr.load(path)[2]
                   if n == "bench.step")
    return path, marks


def test_only_the_runs_own_profile_is_read(tmp_path):
    """A stale profile, or another run's, is passed over for the one whose
    `bench.step` spans the reduced trace holds; with none, no reading."""
    old, old_marks = _profile(tmp_path / "old", 2)
    new, marks = _profile(tmp_path / "new", 3)
    assert len(marks) == 3 and marks != old_marks
    red = {"t0": marks[0][0], "t1": marks[-1][1], "steps": marks}
    prog = pt.of(red, [old, new])
    assert [a["step_num"] for n, _, _, a in prog["spans"]
            if n == "serve.step"] == [0, 1, 2]
    assert red["program"] is prog
    stale = {"t0": marks[0][0], "t1": marks[-1][1], "steps": marks[:2]}
    assert pt.of(stale, [old, new]) is None
    assert host_work_ms_per_step.read({"trace": stale}) is None
