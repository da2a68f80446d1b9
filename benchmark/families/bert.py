"""BERT (``model_type: "bert"``) for the training runner: the program's
``BertForPreTraining`` with its MLM + NSP grad step, and the plain reference
``references/bert.py``."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.harness import flops, traffic, weights
from benchmark.references import bert as reference

decayed = reference.decayed


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models import bert_large_config

    return bert_large_config(
        vocab_size=cfg["held_vocab"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        hidden_dropout=cfg["hidden_dropout_prob"],
        attention_dropout=cfg["attention_probs_dropout_prob"],
        layernorm_eps=cfg["layer_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models import BertForPreTraining

    return BertForPreTraining(program_config(cfg))


def param_shapes(model):
    import jax

    z = jax.ShapeDtypeStruct((1, 8), np.int32)
    return jax.eval_shape(model.init, jax.random.PRNGKey(0), z, z,
                          z)["params"]


def grad_step(model):
    import apex_tpu.models as models

    return models.make_pretrain_step(model)


def batches(cfg: dict, mix: dict, seed: int,
            count: int) -> List[Dict[str, np.ndarray]]:
    return traffic.train_batches(mix, cfg["held_vocab"],
                                 cfg["type_vocab_size"], seed, count)


def train_flops_per_token(cfg: dict, mix: dict) -> float:
    return flops.bert_train_flops_per_token(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], vocab=cfg["held_vocab"],
        seq_len=mix["seq_len"], mlm_k=mix["mlm_per_seq"])


def follow(cfg: dict, mix: dict, seed: int, batches: List[dict],
           precision: str = "float32") -> dict:
    import jax.numpy as jnp

    params = weights.make_weights(reference.param_table(cfg), seed)
    return reference.train(
        params, [{k: jnp.asarray(v) for k, v in b.items()} for b in batches],
        cfg, traffic.train_hyper(mix), precision,
        block_rows=mix.get("reference_block_rows"))


def shapes(cfg: dict, mix: dict, chips: int) -> dict:
    return {"batch": mix["batch"] // chips, "seq_len": mix["seq_len"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "layers": cfg["num_hidden_layers"]}
