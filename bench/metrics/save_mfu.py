"""The whole save's share of the chip's peak: the HBM bytes a save must move
on the device (every byte of the state read once, every byte of the
checkpoint written once) over HBM bandwidth, over the time the traced
window spent in the benchmark's ``bench.save`` spans. It bounds what the
save's kernels, ``quantize_roofline`` among them, can give to
``save_stall_s``. In percent."""
from bench import trace


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    if tr is None or not pk or not ctx["rec"]["saves"]:
        return None
    spans = [(s, e) for s, e, name in tr.marks if name == "bench.save"]
    secs = sum(e - s for s, e in trace.union(spans)) / 1e9
    if not spans or secs <= 0:
        return None
    from bench.harness import checkpoint_bytes
    state = checkpoint_bytes(ctx["struct"], False)
    ckpt = checkpoint_bytes(ctx["struct"], ctx["quantize"])
    least = len(spans) * (state + ckpt) / pk["hbm_bytes_per_s"]
    return 100.0 * least / secs
