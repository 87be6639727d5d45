"""Device time of one decode step: the `_decode_fn` program's device
time over its calls in the traced steps, in ms."""
from bench import trace as tr


def read(ctx):
    calls, sec = tr.module_time(ctx["trace"], "_decode_fn")
    return 1e3 * sec / calls if calls else None
