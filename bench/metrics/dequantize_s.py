"""Seconds per resume of dequantizing the int8 leaves: the program's
``ckpt.dequantize`` spans, one per int8 leaf (upload, kernel, fetch to the
host, cast), compiles included."""
from bench import spans


def read(ctx):
    return spans.per_resume(ctx, spans.named("ckpt.dequantize"))
