"""Seconds per resume of reading the checkpoint out of the burst buffer: the
program's ``ckpt.stage`` (the stage-in request) and ``ckpt.read`` (the
manifest and every leaf's ``pread``) spans."""
from bench import spans


def read(ctx):
    return spans.per_resume(ctx, spans.named("ckpt.stage", "ckpt.read"))
