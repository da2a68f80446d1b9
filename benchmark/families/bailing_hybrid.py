"""``model_type: "bailing_hybrid"`` for the serving runner: the program's
``BailingHybridModel`` (Kimi delta attention layers with one recurrent state
a slot beside the MLA layers' latent pages; a group-limited sigmoid router
over a share of the experts plus an ungated shared expert) and the plain
reference ``references/bailing_hybrid.py``.

A configuration file's ``num_experts`` is what the chip HOLDS; the router's
width is ``router_experts`` (the published count; absent: all are held), the
first held expert ``first_expert``, and the published index of the first
held layer ``first_layer``."""

from __future__ import annotations

import functools
import time
from typing import List

from benchmark.harness import weights
from benchmark.references import bailing_hybrid as reference


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models.bailing_hybrid import BailingHybridConfig

    held = {"rope_scaling": None, "use_kda_lora": False, "no_kda_lora": True,
            "kda_safe_gate": True, "topk_method": "noaux_tc",
            "score_function": "sigmoid", "group_norm_size": 1,
            "gated_attention_proj_granularity_type": "head_wise",
            "use_qk_norm": True, "rope_interleave": True,
            "linear_silu": True, "use_bias": False, "use_qkv_bias": False,
            "tie_word_embeddings": False, "q_lora_rank": None,
            "moe_router_enable_expert_bias": True,
            "scale_router_input": False, "up_proj_norm": False,
            "use_mla_nope": False, "value_norm": False, "use_nGPT": False}
    for key, value in held.items():
        if cfg.get(key) != value:
            raise ValueError(f"{key} {cfg.get(key)!r} is not held: the "
                             f"program holds the published {value!r}")
    if cfg["moe_shared_expert_intermediate_size"] != \
            cfg["moe_intermediate_size"] * cfg["num_shared_experts"]:
        raise ValueError("the shared expert is held as num_shared_experts "
                         "x moe_intermediate_size")
    routed = reference.router_experts(cfg)
    return BailingHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        first_layer=cfg.get("first_layer", 0),
        layer_group_size=cfg["layer_group_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"], kda_head_dim=cfg["head_dim"],
        conv_size=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        dt_bias_center=float(cfg.get("dt_bias_center", 0.0)),
        q_lora_rank=None, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        num_experts=routed, num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        num_shared_experts=cfg["num_shared_experts"],
        experts_held=(cfg["num_experts"]
                      if cfg["num_experts"] != routed else None),
        first_expert=cfg.get("first_expert", 0),
        expert_swiglu_limit=tuple(cfg.get("expert_swiglu_limit_list", ())),
        shared_expert_swiglu_limit=tuple(
            cfg.get("share_expert_swiglu_limit_list", ())),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models.bailing_hybrid import BailingHybridModel

    return BailingHybridModel(program_config(cfg))


def drawn_vocab(cfg: dict) -> int:
    return cfg["vocab_size"]     # the slice of the vocabulary the chip holds


def page_bytes(cfg: dict, page_size: int) -> int:
    """One page of the MLA layers' latent group over its layers: the mix's
    ``pool_bytes`` buys pages of the block table's group.  The KDA layers'
    state is the engine's own on top, sized by the slots alone
    (``kv_pool.state_bytes``)."""
    from apex_tpu.serving import kv_pool

    return kv_pool.page_bytes(program_config(cfg), page_size)


def mixer_params(cfg: dict, kind: str) -> int:
    """One layer's mixer matrices (the taps and the vectors are not
    multiplied with)."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if kind == reference.KDA:
        w = h * cfg["head_dim"]
        return 6 * e * w + e * h
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    return (e * h * (nope + rope) + e * (rank + rope)
            + rank * h * (nope + vd) + e * h + h * vd * e)


def forward_flops_per_token(cfg: dict) -> float:
    """2 x the parameters a token's forward pass multiplies with ON THIS
    CHIP: per layer the mixer's matrices, and the dense SwiGLU or the whole
    router, the shared expert and the routed experts a token is sent to
    that are held here, BY EXPECTATION ``num_experts_per_tok x num_experts
    / router_experts`` (2 of the eight at 128 of 512); the sliced head.
    Attention over the context and the delta rule's own products are left
    out, so the MFU built on this is a lower bound."""
    e, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed = reference.router_experts(cfg)
    held_pairs = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
    moe = (e * routed + 3 * e * m * held_pairs
           + 3 * e * m * cfg["num_shared_experts"])
    dense = 3 * e * cfg["intermediate_size"]
    total = sum(mixer_params(cfg, reference.layer_kind(cfg, n))
                + (dense if reference.is_dense(cfg, n) else moe)
                for n in range(cfg["num_hidden_layers"]))
    return 2.0 * (total + cfg["vocab_size"] * e)


def judge(cfg: dict, seed: int, samples: List[tuple],
          precision: str = "float32", reference_logits=None) -> dict:
    """``gap`` is the MEAN gap over the served tokens, not the widest
    (``reference.mean_gap`` says why); ``where`` is the worst token's.  The
    reference makes its weights group by group (embedding, each layer, the
    head).  ``precision`` is one of ``reference.VARIANTS``."""
    t0 = time.perf_counter()
    out = reference.mean_gap(
        functools.partial(weights.make_weights, seed=seed), samples, cfg,
        precision=precision, reference_logits=reference_logits)
    out["judge_s"] = time.perf_counter() - t0       # weights included
    return out
