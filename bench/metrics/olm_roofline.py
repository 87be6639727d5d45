"""Share of its roofline that the olm kernel reaches in a decode step, in
%: the least time of the step's weight GEMMs (q, k, v, o, gate, up, down
per layer and the head, M = the engine's slots; each the larger of its
operations over the bf16 peak and its float32 operand and result bytes
over HBM bandwidth, bench/work.py) over the kernel's measured time per
decode step. The decode GEMVs are HBM-bound."""
from bench import trace as tr, work
from bench.metrics.olm_kernel_ms import OLM_OP


def read(ctx):
    steps = sum(1 for s in ctx["trace"]["host_steps"] if s["decode_tokens"])
    calls, sec = tr.op_time(ctx["trace"], OLM_OP)
    if not (calls and steps):
        return None
    rows = ctx["traffic"]["engine"]["slots"]
    least = sum(work.gemm_least_time(M, K, N, ctx["peaks"])[0]
                for _, M, K, N in work.gemms(ctx["model"], rows))
    return 100.0 * least / (sec / steps)
