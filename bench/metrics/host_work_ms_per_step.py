"""The engine's own host time per step outside prefill work, in ms: each
traced `serve.step` span (serving/engine.py) minus the parts of it that
its `serve.sync` spans (the blocking reads of logits) and its
`serve.prefill` and `serve.chunk` spans cover, averaged over the traced
steps. A prefill step then reads like a decode step, so the reading does
not swing with how many of the few traced steps prefilled. It counts
host work whether or not the device ran meanwhile; `host_ms_per_step`
counts only the part with the device idle."""
from bench import program_trace as pt
from bench import trace as tr

LEFT_OUT = ("serve.sync", "serve.prefill", "serve.chunk")


def read(ctx):
    steps = pt.spans(ctx, "serve.step")
    if not steps:
        return None
    out = tr.union((s, e) for name in LEFT_OUT
                   for s, e, _ in pt.spans(ctx, name))
    host = [(e - s) - tr.covered(out, s, e) for s, e, _ in steps]
    return 1e3 * sum(host) / len(host)
