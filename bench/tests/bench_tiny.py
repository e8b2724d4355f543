"""The CPU-sized layout the benchmark's tests run: data/tiny."""
import os

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny")


def tiny_cell(name, root=TINY):
    """A cell of the CPU-sized layout under ``data/tiny``."""
    from bench import harness
    return harness.load_cell(name, bench_dir=os.path.join(root, "bench"))
