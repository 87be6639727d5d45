"""Device time of the grouped-query expansion per decode step: the self
time of the ops under the `kv_repeat` name scope (models/layers.py
`_attn_core`: the two `jnp.repeat` calls that copy each KV head to the
query heads of its group) in the traced `_decode_fn` runs, per run, in
ms (bench/program_trace.py). None where the scope holds no op, as in a
program built before the scope or where XLA fused the repeat into the
core's fusions."""
from bench import program_trace as pt


def read(ctx):
    return pt.ms_per_run(ctx, "kv_repeat", "_decode_fn")
