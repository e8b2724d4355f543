"""Seconds per save of fetching the state to the host: the program's
``ckpt.fetch`` spans, one per leaf (its blocking ``device_get`` of the
full-precision value, and the copy to bytes of a leaf saved exactly)."""
from bench import spans


def read(ctx):
    return spans.per_save(ctx, spans.named("ckpt.fetch"))
