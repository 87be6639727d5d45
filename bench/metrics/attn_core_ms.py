"""Device time of the attention core per decode step: the self time of
the ops under the `attn_core` name scope (models/layers.py `_attn_core`:
the grouped-query expansion, the scores, the softmax and the weighted
sum over the paged view) in the traced `_decode_fn` runs, per run, in ms
(bench/program_trace.py)."""
from bench import program_trace as pt


def read(ctx):
    return pt.ms_per_run(ctx, "attn_core", "_decode_fn")
