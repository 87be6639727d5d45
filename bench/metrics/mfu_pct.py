"""Model FLOPs utilization of the whole step, in %: the model FLOPs of
every real token processed (prompt and decode, each at its attention
context; bench/work.py) over the bf16 peak times a time base.

  .batch  the measured window's length (closed loops, where the system
          is kept busy)
  .chat   the summed wall time of the window's engine steps that did
          work (an open loop below its knee processes what it is
          offered, so a window-based share could not move)
"""
from bench import work


def read(ctx):
    m, steps = ctx["model"], ctx["window_steps"]
    mm = 2 * work.matmul_params(m)
    per_ctx = work.attention_flops(m, 1)
    flops = sum((s["prompt_tokens"] + s["decode_tokens"]) * mm
                + (s["prompt_ctx"] + s["decode_ctx"]) * per_ctx
                for s in steps)
    if ctx["variant"] == "chat":
        base = sum(s["t1"] - s["t0"] for s in steps
                   if s["prompt_tokens"] or s["decode_tokens"])
    else:
        base = ctx["close"] - ctx["open"]
    if not flops or base <= 0:
        return None
    return 100.0 * flops / (base * ctx["peaks"]["bf16_flops"])
