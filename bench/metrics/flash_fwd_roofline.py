"""Roofline share of the Pallas flash-attention forward kernel: for every
event of it in the traced window, the least time the chip could take for
causal attention at the cell's shapes (the larger of its FLOPs over the
bf16 peak and its bytes over HBM bandwidth; FLOPs bound it), over the
events' summed device time. In percent."""
from bench import trace

# The kernel's ops as a v5e trace names them, read off one by hand: a
# custom call to "tpu_custom_call" whose result is (bf16 output (B,H,S,D),
# f32 row max, f32 row sum). It has no stable name of its own yet.
KERNEL = (r'^\S+ = \(bf16\[\d+,\d+,\d+,\d+\][^=]* custom-call\('
          r'.*custom_call_target="tpu_custom_call"')


def call_cost(cfg: dict, batch: int, seq: int):
    """(FLOPs, bytes) of one forward call over all heads of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    flops = 4.0 * batch * h * hd * seq * (seq + 1) / 2
    bytes_ = 2.0 * batch * seq * hd * (2 * h + 2 * kv)
    return flops, bytes_


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    if tr is None or not pk:
        return None
    secs, n = trace.kernel_time(tr, KERNEL)
    if not n or secs <= 0:
        return None
    flops, bytes_ = call_cost(ctx["config"], ctx["mix"]["batch"],
                              ctx["mix"]["seq_len"])
    least = max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * n * least / secs
