"""Roofline share of the Pallas int8 quantize kernel: the bytes it must read
(f32 moments) and write (int8 values and f32 block scales) for every save
of the traced window, over HBM bandwidth, over its events' summed device
time. The kernel is bound by bandwidth. In percent."""
import math

from bench import trace

# The kernel's ops as a v5e trace names them, read off one by hand: a
# custom call to "tpu_custom_call" whose result starts with the int8 values.
KERNEL = (r'^\S+ = \(s8\[[^=]* custom-call\('
          r'.*custom_call_target="tpu_custom_call"')
BLOCK = 2048


def save_bytes(ctx) -> float:
    """Bytes one save's quantize calls must move, from the leaf shapes."""
    from bench.harness import int8_allowed, leaf_items
    total = 0.0
    for name, leaf in leaf_items(ctx["struct"]):
        if int8_allowed(name, leaf):
            n = math.prod(leaf.shape)
            total += n * 4 + n + 4 * -(-n // BLOCK)
    return total


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    saves = len(ctx["rec"]["saves"])
    if tr is None or not pk or not saves:
        return None
    secs, n = trace.kernel_time(tr, KERNEL)
    if not n or secs <= 0:
        return None
    least = saves * save_bytes(ctx) / pk["hbm_bytes_per_s"]
    return 100.0 * least / secs
