"""Seconds per resume of the program's restore: its ``ckpt.restore`` span
(stage, read through the file handles, dequantize, place on the device)."""


def read(ctx):
    vals = [s["dur"] for s in ctx["spans"] if s["name"] == "ckpt.restore"]
    n = len(ctx["rec"]["resumes"])
    return sum(vals[-n:]) / n if n and len(vals) >= n else None
