"""Seconds per save of quantizing the int8 leaves: the program's
``ckpt.quantize`` spans, one per int8 leaf (the fetched value uploaded
again, flattened and padded, the kernel, the fetch of its values and
scales and their copy to bytes), compiles included."""
from bench import spans


def read(ctx):
    return spans.per_save(ctx, spans.named("ckpt.quantize"))
