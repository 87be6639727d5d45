"""Device time of the MLP per decode step: the self time of the ops
under the `mlp` name scope (models/transformer.py: gate, up and down
GEMMs, or the MoE) in the traced `_decode_fn` runs, per run, in ms
(bench/program_trace.py)."""
from bench import program_trace as pt


def read(ctx):
    return pt.ms_per_run(ctx, "mlp", "_decode_fn")
