"""apex_tpu — a TPU-native training-acceleration library.

A ground-up JAX/XLA/Pallas rebuild of the capability surface of NVIDIA Apex
(reference: kevinstephano/apex, surveyed in /root/repo/SURVEY.md):

- ``apex_tpu.optimizers``     — fused optimizers (FusedAdam/FusedLAMB/FusedSGD/
  FusedNovoGrad) as flattened-buffer Pallas multi-tensor update kernels behind a
  torch-like ``step()`` facade and optax-style pure transforms.
  (reference: apex/optimizers/*, csrc/multi_tensor_*.cu)
- ``apex_tpu.normalization``  — FusedLayerNorm / FusedRMSNorm Pallas kernels.
  (reference: apex/normalization/fused_layer_norm.py, csrc/layer_norm_cuda_kernel.cu)
- ``apex_tpu.amp``            — mixed-precision opt-levels (O0-O3) as bf16
  precision policies; ``scale_loss`` kept for API parity.
  (reference: apex/amp/*)
- ``apex_tpu.parallel``       — DistributedDataParallel facade, SyncBatchNorm via
  mesh psum, LARC. (reference: apex/parallel/*)
- ``apex_tpu.transformer``    — Megatron-style tensor/sequence/pipeline parallelism
  over a named ``jax.sharding.Mesh``. (reference: apex/transformer/*)
- ``apex_tpu.contrib``        — multihead_attn, xentropy, clip_grad, distributed
  (ZeRO) optimizers, sparsity (ASP), and the long tail.
  (reference: apex/contrib/*)
- ``apex_tpu.ops``            — the Pallas kernel layer (the CUDA ``csrc/``
  equivalent): layer_norm, rms_norm, flash attention, softmax-xentropy,
  multi-tensor optimizer updates.
- ``apex_tpu.collectives``    — the NCCL-equivalent: thin wrappers over XLA
  collectives (psum/all_gather/psum_scatter/ppermute/all_to_all) on mesh axes.
- ``apex_tpu.models``         — model zoo used by benchmarks/examples (BERT, GPT,
  ResNet). (reference: examples/, apex/transformer/testing/standalone_*.py)
"""

__version__ = "0.1.0"

from apex_tpu import collectives  # noqa: F401
from apex_tpu import mesh  # noqa: F401

# Subpackages are imported lazily by users:
#   from apex_tpu import amp, optimizers, normalization, parallel, transformer
