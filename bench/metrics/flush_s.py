"""Seconds per save of the flush to the PFS: the program's ``ckpt.flush``
span, from the end of ingest to the acknowledgement of every server."""


def read(ctx):
    steps = {s["step"] for s in ctx["rec"]["saves"]}
    vals = [s["dur"] for s in ctx["spans"]
            if s["name"] == "ckpt.flush" and s["args"].get("step") in steps]
    return sum(vals) / len(vals) if vals else None
