"""Seconds per save of the burst buffer's ingest: the program's ``ckpt.save``
span (pwrite of every leaf through the file handles, replicated ACKs)."""


def read(ctx):
    steps = {s["step"] for s in ctx["rec"]["saves"]}
    vals = [s["dur"] for s in ctx["spans"]
            if s["name"] == "ckpt.save" and s["args"].get("step") in steps]
    return sum(vals) / len(vals) if vals else None
