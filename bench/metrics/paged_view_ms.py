"""Device time of the paged KV view per decode step: the self time of
the ops under the `paged_view` name scope (models/layers.py: the two
`paged_pool_view` walks of each layer) in the traced `_decode_fn` runs,
per run, in ms (bench/program_trace.py)."""
from bench import program_trace as pt


def read(ctx):
    return pt.ms_per_run(ctx, "paged_view", "_decode_fn")
