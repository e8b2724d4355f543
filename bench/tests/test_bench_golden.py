"""What the benchmark draws and computes for a configuration, pinned: the
seed's weights, the plain reference's losses and the program's ModelConfig
of each tiny configuration, at the CPU size. The values were computed at
commit bd3dbd0, before the layout moved from ``bench/weights.py`` into the
reference module that a configuration names; a change to how the harness
finds those pieces has to reproduce them exactly.

The losses are compared bit for bit and assume XLA's default CPU flags:
``--xla_cpu_multi_thread_eigen=false``, for one, moves the last digits."""
import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from bench import harness, weights
from bench_tiny import tiny_cell
from repro.configs.base import get_config

SEED = 2**31 + 12345

# ModelConfig fields that the mapping every configuration file shares sets
# to the same value in both; every field it does not set is the program's
# own default for the file's ``program_arch``
SHARED = {"vocab_size": 512, "segments": ((("attn",), 2),), "head_dim": None,
          "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}

GOLDEN = {
    "d.train": {
        "weights_sha256":
            "d5fd9b4eb4e191b7750e4308657c65d4b3d7b8d25c009b315e5cfca61fd9dde0",
        "losses": [6.261655807495117, 6.27717399597168, 6.280564785003662],
        "program": {**SHARED, "d_model": 64,
                    "num_heads": 8, "num_kv_heads": 2, "d_ff": 96,
                    "rope_theta": 100000, "tie_embeddings": False,
                    "norm": "rmsnorm", "act": "silu", "mlp_gated": True,
                    "optimizer": "adafactor"},
    },
    "t.train": {
        "weights_sha256":
            "7cf18f3bef278ff2729704dc603f1b837377e376bb190c9a730ad4f307c1a363",
        "losses": [6.171563625335693, 6.169981479644775, 6.179574012756348],
        "program": {**SHARED, "d_model": 64,
                    "num_heads": 4, "num_kv_heads": 2, "d_ff": 128,
                    "rope_theta": 999999.4420358813, "tie_embeddings": True,
                    "norm": "layernorm", "act": "gelu", "mlp_gated": False,
                    "optimizer": "adamw"},
    },
}


def weights_sha256(tree) -> str:
    """Over every leaf in the order of its name: name, dtype, shape, bytes."""
    h = hashlib.sha256()
    for name, leaf in sorted(weights.flatten(tree).items()):
        a = np.asarray(jax.device_get(leaf))
        h.update(f"{name}:{a.dtype}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(GOLDEN))
def test_tiny_configuration_reads_as_pinned(cell):
    want = GOLDEN[cell]
    c = tiny_cell(cell)
    cfg = c.config
    reference = harness.reference_for(cfg)
    tree = weights.init_params(cfg, reference.param_shapes(cfg), SEED)
    assert weights_sha256(tree) == want["weights_sha256"]
    feed = harness.Feed(SEED, cfg, c.mix)
    batches = [feed.host(i) for i in range(harness.REF_STEPS)]
    ref = reference.train(cfg, SEED, batches, steps=harness.REF_STEPS)
    assert ref["losses"] == want["losses"]
    assert harness.program_config(cfg) == dataclasses.replace(
        get_config(cfg["program_arch"]), **want["program"])
