"""The host process's resident peak during a restore, in GB: the
``rss_peak_bytes`` of the program's ``ckpt.restore`` spans, averaged over
the resumes."""


def read(ctx):
    vals = [s["args"]["rss_peak_bytes"] / 1e9 for s in ctx["spans"]
            if s["name"] == "ckpt.restore" and "rss_peak_bytes" in s["args"]]
    return sum(vals) / len(vals) if ctx["rec"]["resumes"] and vals else None
