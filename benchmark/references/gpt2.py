"""Plain reference of GPT-2's forward pass: float32 ``jax.numpy``, no
kernels, no cache, no batching of requests, nothing imported from the
program, weights made from the seed.

Follows Radford et al. 2019 as the public ``GPT2LMHeadModel`` states it:
learned positions, pre-LN blocks, ``gelu_new`` (the tanh form), a causal
mask, the head tied to the token embedding.  Weights are held (out, in) as
the program's Megatron-style linears hold them.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.references.precision import MATMULS


def param_table(cfg: dict) -> Dict[str, tuple]:
    e, v = cfg["n_embd"], cfg["held_vocab"]
    f32 = jnp.float32
    t = {"word_embeddings/weight": ((v, e), f32),
         "position_embeddings": ((cfg["n_positions"], e), f32),
         "final_norm/weight": ((e,), f32), "final_norm/bias": ((e,), f32)}
    for n in range(cfg["n_layer"]):
        p = f"layer_{n}"
        for norm in ("input_norm", "post_norm"):
            t[f"{p}/{norm}/weight"] = ((e,), f32)
            t[f"{p}/{norm}/bias"] = ((e,), f32)
        for name, out, inp in (("qkv", 3 * e, e), ("out_proj", e, e),
                               ("mlp_in", 4 * e, e), ("mlp_out", e, 4 * e)):
            t[f"{p}/{name}/weight"] = ((out, inp), f32)
            t[f"{p}/{name}/bias"] = ((out,), f32)
    return t


def _layer_norm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def logits_at(params, ids, positions, cfg: dict, precision: str = "float32"):
    """Logits [B, K, V] of the next token at ``positions`` [B, K] of the
    sequences ``ids`` [B, S] (padding after a sequence's end changes nothing
    before it: the mask is causal)."""
    mm = MATMULS[precision]
    e, h = cfg["n_embd"], cfg["n_head"]
    d = e // h
    eps = cfg["layer_norm_epsilon"]
    b, s = ids.shape
    x = params["word_embeddings/weight"][ids] \
        + params["position_embeddings"][None, :s]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]

    def block(x, p):
        hn = _layer_norm(x, p["input_norm/weight"], p["input_norm/bias"], eps)
        qkv = mm(hn, p["qkv/weight"].T) + p["qkv/bias"]
        q, k, v = (t.reshape(b, s, h, d).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, e)
        x = x + mm(ctx, p["out_proj/weight"].T) + p["out_proj/bias"]
        hn = _layer_norm(x, p["post_norm/weight"], p["post_norm/bias"], eps)
        mid = jax.nn.gelu(mm(hn, p["mlp_in/weight"].T) + p["mlp_in/bias"],
                          approximate=True)
        return x + mm(mid, p["mlp_out/weight"].T) + p["mlp_out/bias"], None

    # the blocks are alike: scanning one block over the stacked layers keeps
    # the program 36 times smaller to trace and to load than unrolling them
    leaves = sorted(k.split("/", 1)[1] for k in params
                    if k.startswith("layer_0/"))
    stacked = {leaf: jnp.stack([params[f"layer_{n}/{leaf}"]
                                for n in range(cfg["n_layer"])])
               for leaf in leaves}
    x, _ = jax.lax.scan(block, x, stacked)
    x = _layer_norm(x, params["final_norm/weight"],
                    params["final_norm/bias"], eps)
    picked = jnp.take_along_axis(x, positions[..., None], axis=1)
    return mm(picked, params["word_embeddings/weight"].T)


def served_gaps(params, ids, positions, served, cfg: dict,
                precision: str = "float32"):
    """Per position [B, K]: how far the reference's logit of ``served`` lies
    below the reference's best.  With a lower ``precision`` the token judged
    is the one that precision puts first (the control), not ``served``."""
    ref = logits_at(params, ids, positions, cfg)
    if precision != "float32":
        served = jnp.argmax(logits_at(params, ids, positions, cfg, precision),
                            axis=-1)
    chosen = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    return ref.max(-1) - chosen


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items: tuple, precision: str):
    cfg = dict(cfg_items)
    return jax.jit(functools.partial(served_gaps, cfg=cfg,
                                     precision=precision))


def widest_gap(params, samples, cfg: dict, *, precision: str = "float32",
               rows: int = 2) -> dict:
    """``samples`` is a list of (prompt, served tokens).  Every sequence is
    padded to the model's positions and judged in blocks of ``rows``; returns
    the widest gap over all served tokens, and where."""
    import numpy as np

    keys = ("n_embd", "n_head", "n_layer", "layer_norm_epsilon")
    fn = _jitted(tuple((k, cfg[k]) for k in keys), precision)
    length = cfg["n_positions"]
    width = max(len(t) for _, t in samples)
    worst, where, tokens = 0.0, None, 0
    for start in range(0, len(samples), rows):
        block = samples[start:start + rows]
        block = block + [block[-1]] * (rows - len(block))
        ids = np.zeros((rows, length), np.int32)
        pos = np.zeros((rows, width), np.int32)
        tok = np.zeros((rows, width), np.int32)
        valid = np.zeros((rows, width), bool)
        for r, (prompt, out) in enumerate(block):
            seq = np.concatenate([prompt, out[:-1]])
            ids[r, :len(seq)] = seq
            pos[r, :len(out)] = len(prompt) - 1 + np.arange(len(out))
            tok[r, :len(out)] = out
            valid[r, :len(out)] = True
        gaps = np.where(valid, np.asarray(fn(params, ids, pos, tok)), 0.0)
        tokens += int(valid[:len(samples) - start].sum())
        if gaps.max() > worst:
            r, k = np.unravel_index(gaps.argmax(), gaps.shape)
            worst, where = float(gaps.max()), (start + int(r), int(k))
    return {"gap": worst, "where": where, "tokens": tokens}
