"""Device time of the fused olm Pallas kernel per decode step, in ms:
the traced device operations whose name holds OLM_OP, over the decode
steps traced."""
from bench import trace as tr

OLM_OP = "olm_matmul_fused"


def read(ctx):
    steps = sum(1 for s in ctx["trace"]["host_steps"] if s["decode_tokens"])
    calls, sec = tr.op_time(ctx["trace"], OLM_OP)
    return 1e3 * sec / steps if calls and steps else None
