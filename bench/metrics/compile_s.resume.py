"""Seconds per resume spent in backend compiles: the program's
``jax.compile`` spans recorded inside ``ckpt.restore`` and its spans."""
from bench import spans


def read(ctx):
    return spans.per_resume(ctx, spans.compiles_in(spans.RESTORE))
