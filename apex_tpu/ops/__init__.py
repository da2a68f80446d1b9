"""The kernel layer — TPU-native equivalent of the reference's ``csrc/``.

Every op here is a pure function with a ``jax.custom_vjp`` backed by Pallas
TPU kernels (compiled via Mosaic on TPU; interpret mode off-TPU so the same
code paths are unit-testable on CPU). Reference mapping in SURVEY.md §2.2.
"""

from apex_tpu.ops.layer_norm import layer_norm, rms_norm  # noqa: F401
from apex_tpu.ops.flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_with_lse,
    mha_reference,
)
from apex_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention,
    paged_attention_reference,
)
from apex_tpu.ops.paged_latent_attention import (  # noqa: F401
    paged_latent_attention,
    paged_latent_attention_reference,
)
from apex_tpu.ops.paged_write import paged_write  # noqa: F401
from apex_tpu.ops.ring_attention import (  # noqa: F401
    from_zigzag,
    ring_attention,
    ring_attention_zigzag,
    to_zigzag,
)
from apex_tpu.ops.scaled_softmax import (  # noqa: F401
    scaled_masked_softmax,
    scaled_softmax,
    scaled_upper_triang_masked_softmax,
)
from apex_tpu.ops.quant import int8_matmul, quantize_weight  # noqa: F401
from apex_tpu.ops.xentropy import softmax_cross_entropy  # noqa: F401
