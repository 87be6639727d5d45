"""Device time of prefill per thousand prompt tokens: the `_prefill_fn`
and `_chunk_fn` programs' device time in the traced steps, times 1000,
over the prompt tokens (real, not padding) those steps prefilled, in
ms."""
from bench import trace as tr


def read(ctx):
    toks = sum(s["prompt_tokens"] for s in ctx["trace"]["host_steps"])
    c1, s1 = tr.module_time(ctx["trace"], "_prefill_fn")
    c2, s2 = tr.module_time(ctx["trace"], "_chunk_fn")
    if not toks or not (c1 + c2):
        return None
    return 1e3 * (s1 + s2) * 1000.0 / toks
