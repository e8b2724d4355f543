"""The benchmark's arithmetic from shapes, against sums worked by hand."""
import jax
import pytest

from bench import harness, weights
from bench.harness import load_config as full_config
from bench.harness import metric_module


def test_sc2_step_flops_match_hand_sum():
    # the benchmark's dense configuration, deepseek-llm-7b.2l: per layer
    # wq + wo 2 x 4096 x 4096, wk + wv 2 x 4096 x 4096 (32 KV heads),
    # w_gate + w_up + w_down 3 x 4096 x 11008; 2 layers plus the untied
    # head 25600 x 4096: 509,607,936 weights; attention 4 x 4096 x 4097 / 2
    # per token and layer; 3 x (fwd) per token, 4096 tokens
    cfg = full_config("deepseek-llm-7b.2l")
    flops = metric_module("train_step_mfu").step_flops(cfg, 1, 4096)
    weights_ = 2 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 25600 * 4096
    attn = 2 * 4 * 4096 * 4097 / 2
    assert weights_ == 509_607_936
    assert flops == 3 * 4096 * (2 * weights_ + attn)
    assert flops == pytest.approx(1.335e13, rel=2e-3)


@pytest.mark.parametrize("name,want", [("deepseek-llm-7b.2l", 2_460_368_968)])
def test_checkpoint_bytes_match_program(name, want):
    from repro.checkpoint import serializer as ser
    from repro.launch import train
    cfg = full_config(name)
    program = harness.Program(cfg, harness.Hooks())
    tree = train.ckpt_tree(program.struct, 0)
    assert harness.checkpoint_bytes(tree, True) == want
    assert ser.tree_nbytes(tree, ser.default_quant_policy) == want


@pytest.mark.parametrize("name,changed", [
    ("deepseek-llm-7b.2l", {"d_model", "num_heads", "num_kv_heads", "d_ff",
                            "vocab_size", "segments", "rope_theta",
                            "optimizer"}),
    ("deepseek-coder-33b.2l", {"segments"}),
])
def test_configs_are_the_programs(name, changed):
    """The file's sizes build the program's registered architecture with
    only what the file states changed (for the coder model, the depth),
    and the weights' layout is the program's."""
    import dataclasses
    from repro.configs.base import get_config
    cfg = full_config(name)
    mine = harness.program_config(cfg)
    theirs = get_config(cfg["program_arch"])
    got = {f.name for f in dataclasses.fields(mine)
           if getattr(mine, f.name) != getattr(theirs, f.name)}
    assert got == changed
    program = harness.Program(cfg, harness.Hooks())
    got = {n: l.shape for n, l in
           weights.flatten(program.struct.params).items()}
    assert got == weights.param_shapes(cfg)


def test_deepseek_params_match_hand_sum():
    cfg = full_config("deepseek-coder-33b.2l")
    n = sum(int(jax.numpy.prod(jax.numpy.array(s)))
            for s in weights.param_shapes(cfg).values())
    per_layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200 \
        + 2 * 7168
    assert n == 2 * per_layer + 2 * 32256 * 7168 + 7168
    assert n == pytest.approx(1.523e9, rel=1e-3)


def test_deepseek_llm_params_match_hand_sum():
    cfg = full_config("deepseek-llm-7b.2l")
    n = sum(int(jax.numpy.prod(jax.numpy.array(s)))
            for s in weights.param_shapes(cfg).values())
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    assert n == 2 * per_layer + 2 * 25600 * 4096 + 4096
    assert n == 614_486_016
