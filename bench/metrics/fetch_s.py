"""Seconds per save of fetching the checkpoint to the host: the program's
``ckpt.fetch`` spans, one per leaf (every device-to-host copy of the leaf's
payload and its copy into bytes: a leaf saved exactly is fetched whole, an
int8 leaf as its values, bitcast on the device to 32-bit words, and its
block scales)."""
from bench import spans


def read(ctx):
    return spans.per_save(ctx, spans.named("ckpt.fetch"))
