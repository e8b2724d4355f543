"""Seconds per save spent in backend compiles: the program's
``jax.compile`` spans recorded inside a save's spans (mostly
``ckpt.quantize``, whose eager kernel calls compile anew)."""
from bench import spans


def read(ctx):
    return spans.per_save(ctx, spans.compiles_in(spans.SAVE))
