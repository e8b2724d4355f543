"""Seconds per save of quantizing the int8 leaves where the state lies: the
program's ``ckpt.quantize`` spans, one per int8 leaf (the jitted flatten,
pad and kernel of the leaf on the device, and the bitcast of its values to
32-bit words, until the results are ready; nothing crosses the host link
inside it), compiles included."""
from bench import spans


def read(ctx):
    return spans.per_save(ctx, spans.named("ckpt.quantize"))
