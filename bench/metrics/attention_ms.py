"""Device time of attention per decode step: the self time of the ops
under the `attn` name scope (models/transformer.py: the q, k, v and o
GEMMs, the paged KV write and view, the attention core) in the traced
`_decode_fn` runs, per run, in ms (bench/program_trace.py)."""
from bench import program_trace as pt


def read(ctx):
    return pt.ms_per_run(ctx, "attn", "_decode_fn")
