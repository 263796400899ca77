"""Shapes and counts of the dense decoder with tied embedding and head:
``shapes/llama_dense.py`` less the ``head`` leaf, so every layer's leaves
sit one index lower and draw other weights.  A test's architecture
(``tests/test_loader.py``): it has no configuration in BENCHMARK.json."""
from harness import spec

_dense = spec.load_shapes("llama_dense")

KERNELS, SCOPES, LOOP = _dense.KERNELS, _dense.SCOPES, _dense.LOOP
MATMUL_SCOPES, SAMPLE_SCOPES = _dense.MATMUL_SCOPES, _dense.SAMPLE_SCOPES
POOL_SCOPES = _dense.POOL_SCOPES
dims, pool_shapes = _dense.dims, _dense.pool_shapes
# the head's product reads the embedding once: the same count
step_matmuls, attention_row = _dense.step_matmuls, _dense.attention_row


def leaves(cfg: dict) -> list:
    return [leaf for leaf in _dense.leaves(cfg) if leaf[0] != "head"]
