"""Mixture-of-experts with expert parallelism (beyond reference).

The reference apex has no MoE; EP completes this framework's parallelism
surface (SURVEY.md §2.4 footnote). See layer.py for the TPU-first design.
"""

from apex_tpu.transformer.moe.layer import (MoEAuxLosses, MoEMLP,
                                            collect_sown_aux,
                                            compute_dispatch_combine,
                                            make_moe_mlp,
                                            moe_layer_selected,
                                            slice_expert_shards)
from apex_tpu.transformer.moe.dropless import (ROUTING_COLLECTION,
                                               ROUTING_STATS,
                                               SHARE_ROUTING_STATS,
                                               DroplessMoEMLP,
                                               grouped_experts)
from apex_tpu.transformer.moe.router import (SigmoidBiasTopKRouter,
                                             SoftmaxTopKRouter, TopKRouter,
                                             load_balancing_loss,
                                             router_z_loss)

__all__ = [
    "MoEAuxLosses", "MoEMLP", "collect_sown_aux",
    "compute_dispatch_combine", "make_moe_mlp", "moe_layer_selected",
    "slice_expert_shards",
    "TopKRouter", "load_balancing_loss", "router_z_loss",
    "SigmoidBiasTopKRouter", "SoftmaxTopKRouter", "DroplessMoEMLP", "grouped_experts",
    "ROUTING_COLLECTION", "ROUTING_STATS", "SHARE_ROUTING_STATS",
]
