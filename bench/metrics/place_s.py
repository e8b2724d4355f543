"""Seconds per resume of placing the restored leaves on the device: the
program's ``ckpt.place`` spans, one per leaf (its ``device_put``, which
returns once the transfer is issued)."""
from bench import spans


def read(ctx):
    return spans.per_resume(ctx, spans.named("ckpt.place"))
