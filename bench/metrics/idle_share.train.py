"""Share of the traced window in which no operation ran on the device, in a
cell that trains (1 - busy / window, busy the union of op intervals)."""
from bench import trace


def read(ctx):
    return trace.idle_share(ctx["trace"]) if ctx["trace"] else None
