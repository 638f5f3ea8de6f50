"""TPU compute kernels for the device data plane.

Two backends per op, mirroring the repo-wide optional-native policy
(ref: BUILDING.txt:173-183 — optional native acceleration with a portable
fallback):

1. a portable ``jax.numpy`` implementation that runs anywhere (CPU mesh
   tests, interpreters), and
2. where it pays, a Pallas TPU kernel fused for MXU/VMEM locality.

Everything here is functional and jit-safe: static shapes, no Python
control flow on traced values.
"""

from hadoop_tpu.ops.activations import swiglu, gelu
from hadoop_tpu.ops.norms import rms_norm, layer_norm
from hadoop_tpu.ops.rope import (apply_rope, rope_frequencies,
                                 yarn_frequencies, yarn_mscale)
from hadoop_tpu.ops.attention import causal_attention
from hadoop_tpu.ops.cross_entropy import (
    softmax_cross_entropy,
    vocab_parallel_cross_entropy,
)

__all__ = [
    "swiglu",
    "gelu",
    "rms_norm",
    "layer_norm",
    "apply_rope",
    "rope_frequencies",
    "yarn_frequencies",
    "yarn_mscale",
    "causal_attention",
    "softmax_cross_entropy",
    "vocab_parallel_cross_entropy",
]
