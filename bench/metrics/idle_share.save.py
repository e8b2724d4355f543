"""Share of the traced window in which no operation ran on the device, in a
cell that saves checkpoints while it trains."""
from bench import trace


def read(ctx):
    return trace.idle_share(ctx["trace"]) if ctx["trace"] else None
