"""Host time per engine step: each traced `bench.step` span minus the
device-busy time inside it, averaged over the traced steps, in ms."""
from bench import trace as tr


def read(ctx):
    red = ctx["trace"]
    steps = red["steps"]
    if not steps:
        return None
    host = [(e - s) - tr.covered(red["busy"], s, e) for s, e in steps]
    return 1e3 * sum(host) / len(host)
