"""The serving engine's observability: host spans on the profiler's clock,
work counters in `ServeEngine.counters`, and the `attn` / `mlp` /
`paged_view` / `attn_core` / `gqa_grouped` name scopes in the compiled
decode and chunk programs."""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.numerics import DotEngine
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.serving.engine import WORK_COUNTERS, Request, ServeEngine
from repro.serving.faults import TransientPrefillError

VOCAB = 512


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=VOCAB,
                      param_dtype="float32", compute_dtype="float32")
    model = Model(cfg, DotEngine(mode="native"))
    return model, model.init(jax.random.PRNGKey(0))


def _engine(tiny, lens, **kw):
    model, params = tiny
    eng = ServeEngine(model, params, **kw)
    rng = np.random.default_rng(0)
    for rid, n in enumerate(lens):
        eng.submit(Request(rid=rid, max_new_tokens=3,
                           prompt=rng.integers(1, VOCAB, n).astype(np.int32)))
    return eng


def _events(log_dir):
    """(name, start_ns, end_ns, {stat: value}, line) of every host event
    named `serve.*` in the profile under `log_dir`."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, i)))
    return out


def _inside(inner, outer):
    return (inner[4] == outer[4] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.mark.parametrize("lens,kw,fail,real,computed", [
    # one bucketed batch: 2 rows of a 5-token bucket padded to 8 -> 2 x 8
    ([3, 5], dict(prefill_bucket_min=4), (), 8, 16),
    # 3 rows pad to 4, lengths to the bucket floor 8 -> 4 x 8
    ([2, 3, 6], {}, (), 11, 32),
    # rows 3 and 5 batch (2 x 8); the 13-token prompt goes in chunks of
    # 8: 8 real + 5 real, 16 computed
    ([3, 5, 13], dict(prefill_bucket_min=4, prefill_chunk=8), (), 21, 32),
    # the second chunk fails and the prompt restarts from chunk 0: its
    # 13 real tokens count once, the chunk recomputed counts again
    ([13], dict(prefill_chunk=8, prefill_backoff=1), (1,), 13, 24),
])
def test_prefill_work_counters_exact(tiny, lens, kw, fail, real, computed):
    eng = _engine(tiny, lens, slots=4, max_len=32, kv_block_size=4, **kw)
    calls = iter(range(100))

    def gate(step, reqs):
        if next(calls) in fail:
            raise TransientPrefillError("injected")

    eng.prefill_fault = gate
    done = eng.run()
    assert len(done) == len(lens)
    assert eng.counters["prefill_tokens"] == real == sum(lens)
    assert eng.counters["prefill_tokens_computed"] == computed
    events = {k: v for k, v in eng.counters.items()
              if k not in WORK_COUNTERS}
    assert events == {"length": len(lens),
                      **({"prefill_retries": len(fail)} if fail else {})}


def test_spans_nest_on_the_host_plane(tiny, tmp_path):
    eng = _engine(tiny, [3, 5, 13], slots=4, max_len=32, kv_block_size=4,
                  prefill_bucket_min=4, prefill_chunk=8)
    done = []
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            eng.step(done)
    ev = _events(str(tmp_path))
    names = {e[0] for e in ev}
    assert names == {"serve.step", "serve.schedule", "serve.prefill",
                     "serve.chunk", "serve.decode", "serve.sync"}
    steps = [e for e in ev if e[0] == "serve.step"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2, 3]
    decodes = [e for e in ev if e[0] == "serve.decode"]
    syncs = [e for e in ev if e[0] == "serve.sync"]
    assert decodes and all(e[3]["lanes"] >= 1 for e in decodes)
    assert any(_inside(d, s) and any(_inside(y, d) for y in syncs)
               for s in steps for d in decodes)
    prefill, = [e[3] for e in ev if e[0] == "serve.prefill"]
    assert prefill == {"rows": 2, "rows_computed": 2, "tokens": 8,
                       "tokens_computed": 16}
    chunks = [e[3] for e in ev if e[0] == "serve.chunk"]
    assert sum(a["tokens"] for a in chunks) == 13
    assert all(a["tokens_computed"] == 8 for a in chunks)
    # every span lies inside a step: the schedule and decode phases
    assert all(any(_inside(e, s) for s in steps) for e in ev
               if e[0] != "serve.step")


def _decode_hlo(tiny):
    """The optimized HLO text of the tiny engine's compiled decode step."""
    eng = _engine(tiny, [5], slots=2, max_len=32, kv_block_size=4)
    eng.step([])
    return eng._decode.lower(
        eng.params, jnp.asarray(eng.last_tok), jnp.asarray(eng.pos),
        eng.cache, eng.memory).compile().as_text()


def test_decode_program_carries_the_scopes(tiny):
    hlo = _decode_hlo(tiny)
    parts = {p for op in re.findall(r'op_name="([^"]*)"', hlo)
             for p in op.split("/")}
    assert {"attn", "mlp", "paged_view"} <= parts
    view = [op for op in re.findall(r'op_name="([^"]*)"', hlo)
            if "/paged_view/" in op]
    assert view and all("/attn/" in op for op in view)


def test_paged_view_is_one_gather_not_a_loop(tiny):
    """The paged KV view reads the pool with indexed ops under its
    `paged_view` scope, and no loop: a `while` there would walk the
    block table one dynamic slice at a time."""
    view = [line for line in _decode_hlo(tiny).splitlines()
            if re.search(r'op_name="[^"]*/paged_view/', line)]
    assert view
    assert [line for line in view if re.search(r"\swhile\(", line)] == []
    assert any(re.search(r'op_name="[^"]*/paged_view/[^"]*gather"', line)
               for line in view)


@pytest.fixture(scope="module")
def grouped():
    """ChatGLM3's grouping at a test size: 32 query heads over 2 KV heads,
    half RoPE, QKV bias."""
    cfg = ModelConfig(name="g", family="dense", n_layers=2, d_model=256,
                      n_heads=32, n_kv_heads=2, d_ff=64, vocab_size=VOCAB,
                      rope_style="half", qkv_bias=True,
                      param_dtype="float32", compute_dtype="float32")
    model = Model(cfg, DotEngine(mode="native"))
    return model, model.init(jax.random.PRNGKey(0))


def _chunk_hlo(grouped):
    """The optimized HLO text of a compiled prefill chunk (8 tokens into
    a 16-position row cache)."""
    model, params = grouped
    eng = ServeEngine(model, params, slots=2, max_len=32, kv_block_size=4,
                      prefill_chunk=8)
    return eng._prefill_chunk.lower(
        eng.params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
        model.init_cache(1, 16), jnp.asarray(0, jnp.int32),
        jnp.asarray([7], jnp.int32)).compile().as_text()


def _op_names(hlo):
    return re.findall(r'op_name="([^"]*)"', hlo)


def test_attention_core_scopes_in_the_decode_and_chunk_programs(grouped):
    """On one device the 32-over-2 grouping takes the grouped core in the
    decode and chunk programs: its ops sit under `attn_core/gqa_grouped/`,
    none under the fallback's `kv_repeat`, and no broadcast copies the
    (2, 32, 2, 8) paged view out to (2, 32, 2, 16, 8)."""
    decode_hlo = _decode_hlo(grouped)
    decode = _op_names(decode_hlo)
    core = [op for op in decode if "attn_core/" in op]
    assert core and all(re.search(r"(^|/)attn/(.*/)?attn_core/", op)
                        for op in core)
    for ops in (decode, _op_names(_chunk_hlo(grouped))):
        assert any("attn_core/gqa_grouped/" in op for op in ops)
        assert not any("kv_repeat" in op for op in ops)
    assert not re.search(r"\[\d+,\d+,2,16,\d+\]\{[^}]*\} broadcast\(",
                         decode_hlo)


def _without_metadata(hlo):
    """The HLO text without each instruction's metadata and without the
    tables of source files, functions and lines at its end."""
    return re.sub(r",? metadata=\{[^}]*\}", "",
                  hlo.split("\nFileNames\n")[0])


def test_attention_core_scopes_change_only_metadata(grouped, monkeypatch):
    """The same decode and chunk programs, compiled with the core's two
    scopes and without them, differ in their metadata alone."""
    scoped = [_decode_hlo(grouped), _chunk_hlo(grouped)]
    named_scope = jax.named_scope

    def leave_out_core_scopes(name):
        if name in ("attn_core", "gqa_grouped", "kv_repeat"):
            return contextlib.nullcontext()
        return named_scope(name)

    monkeypatch.setattr(jax, "named_scope", leave_out_core_scopes)
    plain = [_decode_hlo(grouped), _chunk_hlo(grouped)]
    assert not any("attn_core" in op for hlo in plain
                   for op in _op_names(hlo))
    assert all(any("attn_core" in op for op in _op_names(hlo))
               for hlo in scoped)
    assert [_without_metadata(h) for h in scoped] == \
        [_without_metadata(h) for h in plain]
