"""``model_type: "glm4_moe_lite"`` for the serving runner: the program's
``Glm4MoeLiteModel`` (latent attention over a paged latent pool, dropless
routed experts) and the plain reference ``references/glm4_moe_lite.py``."""

from __future__ import annotations

import functools
import time
from typing import List

from benchmark.harness import weights
from benchmark.references import glm4_moe_lite as reference


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig

    return Glm4MoeLiteConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models.glm4_moe_lite import Glm4MoeLiteModel

    return Glm4MoeLiteModel(program_config(cfg))


def drawn_vocab(cfg: dict) -> int:
    return cfg["vocab_size"]          # the whole vocabulary is held


def page_bytes(cfg: dict, page_size: int) -> int:
    from apex_tpu.serving import kv_pool

    return kv_pool.page_bytes(program_config(cfg), page_size)


def attention_params(cfg: dict) -> int:
    """MLA's matrices of one layer (the two norms' 1280 scales left out)."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (e * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope)
            + e * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * h * (nope + vd) + h * vd * e)


def forward_flops_per_token(cfg: dict) -> float:
    """2 x the parameters a token's forward pass multiplies with: per layer
    the attention's matrices; the dense layers' SwiGLU; per expert layer the
    router, the shared experts and the ``num_experts_per_tok`` routed
    experts a token is sent to (the ACTIVE ones); the head.  Attention over
    the context is left out, so the MFU built on this is a lower bound."""
    e = cfg["hidden_size"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    dense_layers = cfg["first_k_dense_replace"]
    expert_layers = cfg["num_hidden_layers"] - dense_layers
    per_expert_layer = (e * cfg["n_routed_experts"] + expert * (
        cfg["n_shared_experts"] + cfg["num_experts_per_tok"]))
    return 2.0 * (cfg["num_hidden_layers"] * attention_params(cfg)
                  + dense_layers * 3 * e * cfg["intermediate_size"]
                  + expert_layers * per_expert_layer
                  + cfg["vocab_size"] * e)


def judge(cfg: dict, seed: int, samples: List[tuple],
          precision: str = "float32", reference_logits=None) -> dict:
    """``gap`` is the MEAN gap over the served tokens, not the widest
    (``reference.mean_gap`` says why: a routed model is discontinuous in
    its router, and the widest gap of a correct bfloat16 program reads like
    the float8 control's); ``where`` is the worst token's.  The reference
    makes its weights group by group (embedding, each layer, the head): the
    float32 tree would not fit beside the hidden states.  ``precision`` is
    one of ``reference.VARIANTS``."""
    t0 = time.perf_counter()
    out = reference.mean_gap(
        functools.partial(weights.make_weights, seed=seed), samples, cfg,
        precision=precision, reference_logits=reference_logits)
    out["judge_s"] = time.perf_counter() - t0       # weights included
    return out
