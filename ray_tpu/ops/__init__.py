"""TPU compute ops: norms, rotary embeddings, attention, the delta
rule's two forms, losses.

The hot paths (attention, the KDA layers' decode step and chunkwise
prefill) have pallas TPU kernels with jnp reference implementations
used for CPU testing and as autodiff/numerics oracles.
"""

from ray_tpu.ops.norms import rms_norm  # noqa: F401
from ray_tpu.ops.rope import rotary_embedding, apply_rotary  # noqa: F401
from ray_tpu.ops.attention import attention, attention_reference  # noqa: F401
from ray_tpu.ops.losses import softmax_cross_entropy  # noqa: F401
