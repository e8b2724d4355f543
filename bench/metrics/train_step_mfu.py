"""Model FLOP utilization of the train step: the operations a forward and
backward pass require (no recomputation) for the steps of the window's
steps-only stretches, over those stretches' host-clock seconds, over chips
times the bf16 peak. In percent."""


def step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward FLOPs of one step: 3 x (2 x matmul weights per
    token + causal attention), the embedding lookup not counted."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, f = cfg["num_key_value_heads"], cfg["intermediate_size"]
    hd = cfg.get("head_dim", d // h)
    v = -(-cfg["vocab_size"] // 256) * 256
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + d * f * (
        3 if cfg["mlp_gated"] else 2)
    weights = cfg["num_hidden_layers"] * per_layer + d * v
    attn = cfg["num_hidden_layers"] * 4 * h * hd * (seq + 1) / 2
    return 3.0 * batch * seq * (2 * weights + attn)


def read(ctx):
    stretches = ctx["rec"]["stretches"]
    if not stretches or not ctx["peaks"]:
        return None
    steps = sum(n for n, _ in stretches)
    secs = sum(s for _, s in stretches)
    flops = step_flops(ctx["config"], ctx["mix"]["batch"],
                       ctx["mix"]["seq_len"]) * steps
    return 100.0 * flops / secs / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
