"""The readers of the attention core's scopes (models/layers.py
`_attn_core`): `attn_core_ms` and, nested inside it, `kv_repeat_ms`, on a
synthetic profile of one or two decode runs."""
import pytest

from bench import program_trace as pt
from bench.metrics import attention_ms, attn_core_ms, kv_repeat_ms

DECODE = "jit__decode_fn(21)"
LAYER = "jit(_decode_fn)/while/body/closed_call/attn/"
STACKS = {"while.5": "jit(_decode_fn)/while",
          "fusion.1": LAYER + "dot_general",
          "gather.2": LAYER + "paged_view/gather",
          "broadcast_in_dim.3": LAYER + "attn_core/kv_repeat/broadcast_in_dim",
          "broadcast_in_dim.4": LAYER + "attn_core/kv_repeat/broadcast_in_dim",
          "multiply_reduce_fusion.5": LAYER + "attn_core/dot_general",
          "fusion.6": "jit(_decode_fn)/while/body/closed_call/mlp/dot_general"}


def _run(t):
    """One decode run of 10 s from `t`: the layer scan holding a q/k/v
    GEMM (1 s), the view's gather (1 s), the two repeats (2 s and 1 s),
    the core (1.5 s) and an MLP GEMM (2 s)."""
    return [("while.5", t, t + 9.0),
            ("fusion.1", t + 0.5, t + 1.5),
            ("gather.2", t + 1.5, t + 2.5),
            ("broadcast_in_dim.3", t + 2.5, t + 4.5),
            ("broadcast_in_dim.4", t + 4.5, t + 5.5),
            ("multiply_reduce_fusion.5", t + 5.5, t + 7.0),
            ("fusion.6", t + 7.0, t + 9.0)]


def _ctx(stacks=STACKS, runs=2):
    ops = [op for i in range(runs) for op in _run(10.0 * i)]
    programs = [(DECODE, 10.0 * i, 10.0 * i + 10.0) for i in range(runs)]
    self_s, calls = pt.self_times(ops, 0.0, 10.0 * runs, programs)
    prog = {"spans": [], "self": self_s, "calls": calls, "buf": None,
            "hlo": {}, "stacks": {DECODE: dict(stacks)}}
    return {"trace": {"t0": 0.0, "t1": 10.0 * runs, "program": prog},
            "variant": "longdoc"}


@pytest.mark.parametrize("runs", [1, 2])
def test_milliseconds_per_decode_run(runs):
    ctx = _ctx(runs=runs)
    assert kv_repeat_ms.read(ctx) == pytest.approx(3e3)
    assert attention_ms.read(ctx) == pytest.approx(6.5e3)


def test_the_core_holds_the_repeat():
    """Nesting is counted inclusively: the core's time is its own ops
    and the repeat's, and both lie inside attention."""
    ctx = _ctx()
    assert attn_core_ms.read(ctx) == pytest.approx(3e3 + 1.5e3)
    assert kv_repeat_ms.read(ctx) <= attn_core_ms.read(ctx) <= \
        attention_ms.read(ctx)


def test_none_where_the_scope_holds_no_op():
    """A program built before the scopes, or one whose repeat XLA fused
    into the core: no metric, and no error."""
    before = {op: stack.replace("attn_core/kv_repeat/", "").replace(
        "attn_core/", "") for op, stack in STACKS.items()}
    ctx = _ctx(stacks=before)
    assert kv_repeat_ms.read(ctx) is None
    assert attn_core_ms.read(ctx) is None
    assert attention_ms.read(ctx) == pytest.approx(6.5e3)
    fused = {op: stack.replace("kv_repeat/", "")
             for op, stack in STACKS.items()}
    ctx = _ctx(stacks=fused)
    assert kv_repeat_ms.read(ctx) is None
    assert attn_core_ms.read(ctx) == pytest.approx(4.5e3)
    none = {"trace": {"t0": 0.0, "t1": 1.0, "program": None}}
    assert kv_repeat_ms.read(none) is None and attn_core_ms.read(none) is None
