"""Seconds per save spent before the burst buffer's ingest span opens:
fetching, quantizing and serializing the state (the manager's ``ingest_s``,
which includes them, less its ``ckpt.save`` span)."""


def read(ctx):
    spans = {s["args"].get("step"): s["dur"] for s in ctx["spans"]
             if s["name"] == "ckpt.save"}
    vals = [ctx["mgr_metrics"][s["step"]]["ingest_s"] - spans[s["step"]]
            for s in ctx["rec"]["saves"] if s["step"] in spans]
    return sum(vals) / len(vals) if vals else None
