"""The host process's resident peak during a save, in GB: for each save of
the window the larger ``rss_peak_bytes`` of its two roots
(``ckpt.serialize``, ``ckpt.save``), averaged over the saves."""


def read(ctx):
    steps = {s["step"] for s in ctx["rec"]["saves"]}
    peaks = {}
    for s in ctx["spans"]:
        step = s["args"].get("step")
        if s["name"] in ("ckpt.serialize", "ckpt.save") and step in steps \
                and "rss_peak_bytes" in s["args"]:
            peaks[step] = max(peaks.get(step, 0),
                              s["args"]["rss_peak_bytes"] / 1e9)
    return sum(peaks.values()) / len(peaks) if peaks else None
