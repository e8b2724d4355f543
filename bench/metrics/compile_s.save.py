"""Seconds per save spent in backend compiles: the program's
``jax.compile`` spans recorded inside a save's spans (mostly
``ckpt.quantize``, whose jitted programs, one per int8 leaf shape, compile
or load from the persistent cache the first time the process meets them)."""
from bench import spans


def read(ctx):
    return spans.per_save(ctx, spans.compiles_in(spans.SAVE))
