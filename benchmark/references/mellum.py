"""Plain reference of the ``mellum`` decoder's forward pass: float32
``jax.numpy``, no kernels, no cache, no batching of requests, nothing
imported from the program, weights made from the seed group by group (the
float32 tree of the cell's configuration is 15 GB and never exists whole).

The equations (ISSUE 35; the public ``Mellum2-12B-A2.5B-Instruct`` config).
Pre-norm residual blocks, RMSNorm, SiLU, no biases, untied head.

- Attention: ``[q; k; v] = n W_qkv^T`` in 32 query heads and 4 key/value
  heads of 128 (query head ``j`` reads key/value head ``j // 8``); scores
  ``q . k / sqrt(128)`` under a DENSE mask: causal, and on a
  ``sliding_attention`` layer also ``j > i - sliding_window``; softmax;
  ``out = concat_h(P_h v) W_o^T``.
- RoPE by ``rope_parameters[layer_types[i]]``, rotate-half pairing ``(d, d
  + 64)``, the tables written out here: ``default`` is ``theta^(-2i/128)``;
  ``yarn`` blends, per pair, ``inv_freq / factor`` and ``inv_freq`` over the
  linear ramp between the pairs that make ``beta_fast`` and ``beta_slow``
  turns in ``original_max_position_embeddings`` positions (the bounds
  floored and ceiled, as ``transformers`` truncates them), and multiplies
  cos and sin by ``attention_factor``.  Static: not by the length seen.
- Every layer routed: ``p = softmax(n' W_r^T)`` over all experts, the
  ``num_experts_per_tok`` largest, normalised to sum 1 where
  ``norm_topk_prob``; ``y = sum_i w_i SwiGLU_i(n')``.  Every expert runs
  over every token here and the unchosen ones are weighted 0: no token is
  dropped because none is ever dispatched.

Weights are held (out, in) like the program's linears, the routed experts
stacked (experts, in, out).  The table states the configuration's
``param_dtype`` (bfloat16: the model is published in it), so the reference
computes in float32 on the same rounded values the program holds.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.glm4_moe_lite import teacher_forced, token_gaps
from benchmark.references.precision import MATMULS

Q_BLOCK = 512           # queries per block of the attention
PAD_TO = 2048           # sequences are padded to a multiple (causal: free)

#: what the comparison can put in the reference's place (``judge``'s
#: ``precision``): the float8 control, and three faults of the program's own:
#: the band left out of the sliding layers, YaRN left out of the full layers
#: (plain RoPE), the chosen experts' weights not renormalised
VARIANTS = ("float32", "fp8", "no_band", "no_yarn", "no_renorm")


def held_dtype(cfg: dict):
    return jnp.dtype(cfg.get("param_dtype", "bfloat16"))


def layer_table(cfg: dict, n: int) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of layer ``n``, named as the program's
    parameter tree names them."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    x, m = cfg["num_experts"], cfg["moe_intermediate_size"]
    bf = held_dtype(cfg)
    p = f"layer_{n}"
    return {f"{p}/input_norm/weight": ((e,), bf),
            f"{p}/post_norm/weight": ((e,), bf),
            f"{p}/attn/qkv_proj/weight": (((h + 2 * kv) * d, e), bf),
            f"{p}/attn/o_proj/weight": ((e, h * d), bf),
            f"{p}/moe/router/weight": ((x, e), bf),
            f"{p}/moe/experts/gate_proj": ((x, e, m), bf),
            f"{p}/moe/experts/up_proj": ((x, e, m), bf),
            f"{p}/moe/experts/down_proj": ((x, m, e), bf)}


def embed_table(cfg: dict) -> Dict[str, tuple]:
    return {"embed_tokens/weight":
            ((cfg["vocab_size"], cfg["hidden_size"]), held_dtype(cfg))}


def head_table(cfg: dict) -> Dict[str, tuple]:
    return {"final_norm/weight": ((cfg["hidden_size"],), held_dtype(cfg)),
            "lm_head/weight":
            ((cfg["vocab_size"], cfg["hidden_size"]), held_dtype(cfg))}


def param_table(cfg: dict) -> Dict[str, tuple]:
    """The whole tree's table (the tests' small sizes make it at once)."""
    t = dict(embed_table(cfg))
    for n in range(cfg["num_hidden_layers"]):
        t.update(layer_table(cfg, n))
    t.update(head_table(cfg))
    return t


def inv_freq(dim: int, rope: dict) -> np.ndarray:
    """The ``dim // 2`` frequencies of one ``rope_parameters`` entry."""
    theta = float(rope["rope_theta"])
    plain = (1.0 / np.float32(theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ).astype(np.float32)
    if rope["rope_type"] == "default":
        return plain
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")

    def pair_that_turns(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    # 0 below ``low`` (fast pairs: kept), 1 above ``high`` (slow pairs:
    # interpolated), linear between
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - np.float32(low))
                   / np.float32(high - low), 0, 1)
    return (plain / np.float32(rope["factor"]) * ramp
            + plain * (1 - ramp)).astype(np.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, freqs, factor):
    """Rotate-half RoPE over the last dim of ``x`` [S, heads, d]."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return (x * (jnp.cos(ang) * factor)
            + jnp.concatenate([-x2, x1], -1) * (jnp.sin(ang) * factor))


def attention(x, p, cfg: dict, kind: str, mm, variant: str):
    """GQA over one sequence ``x`` [S, e], queries in blocks, dense mask."""
    s = x.shape[0]
    d, h, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    rep = h // kv
    rope = cfg["rope_parameters"][kind]
    if variant == "no_yarn" and rope["rope_type"] == "yarn":
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"]}
    freqs = inv_freq(d, rope)
    factor = jnp.float32(rope.get("attention_factor", 1.0)
                         if rope["rope_type"] == "yarn" else 1.0)
    window = cfg["sliding_window"] if kind == "sliding_attention" \
        and variant != "no_band" else None
    pos = jnp.arange(s, dtype=jnp.int32)
    qkv = mm(x, p["attn/qkv_proj/weight"].T).reshape(s, h + 2 * kv, d)
    q = _rope(qkv[:, :h], pos, freqs, factor)
    k = _rope(qkv[:, h:h + kv], pos, freqs, factor)
    v = qkv[:, h + kv:]
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    blocks = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    rows = s // blocks
    k_t = k.transpose(1, 2, 0)[:, None]                    # [kv, 1, d, S]
    v_t = v.transpose(1, 0, 2)[:, None]                    # [kv, 1, S, d]

    def block(args):
        qb, qpos = args                          # [rows, h, d], [rows]
        qb = qb.reshape(rows, kv, rep, d).transpose(1, 2, 0, 3)
        scores = mm(qb, k_t) * scale             # [kv, rep, rows, S]
        mask = pos[None, :] <= qpos[:, None]
        if window is not None:
            mask = jnp.logical_and(mask, pos[None, :] > qpos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return mm(probs, v_t).transpose(2, 0, 1, 3).reshape(rows, h * d)

    ctx = jax.lax.map(block, (q.reshape(blocks, rows, h, d),
                              pos.reshape(blocks, rows)))
    return mm(ctx.reshape(s, h * d), p["attn/o_proj/weight"].T)


def route(x, p, cfg: dict, mm, variant: str):
    """``[S, experts]`` weights of the routed experts, 0 where not chosen."""
    probs = jax.nn.softmax(mm(x, p["moe/router/weight"].T), axis=-1)
    w, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"] and variant != "no_renorm":
        w = w / w.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(w)


def expert_layer(x, p, cfg: dict, mm, variant: str):
    weights = route(x, p, cfg, mm, variant)

    def one(y, e):
        gate, up, down, w = e
        return y + w[:, None] * mm(
            jax.nn.silu(mm(x, gate)) * mm(x, up), down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["moe/experts/gate_proj"], p["moe/experts/up_proj"],
        p["moe/experts/down_proj"], weights.T))
    return y


def layer(x, p, cfg: dict, kind: str, precision: str = "float32"):
    """One block over one sequence ``x`` [S, e]; ``p`` holds the layer's
    leaves without the ``layer_n/`` prefix, in float32."""
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, p["input_norm/weight"], eps), p, cfg,
                      kind, mm, precision)
    return x + expert_layer(_rms_norm(x, p["post_norm/weight"], eps), p, cfg,
                            mm, precision)


def head(x, p, cfg: dict, precision: str = "float32"):
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    return mm(_rms_norm(x, p["final_norm/weight"], cfg["rms_norm_eps"]),
              p["lm_head/weight"].T)


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_text: str, kind: str, precision: str):
    import json

    cfg = json.loads(cfg_text)
    if what == "layer":
        return jax.jit(functools.partial(layer, cfg=cfg, kind=kind,
                                         precision=precision))
    return jax.jit(functools.partial(head, cfg=cfg, precision=precision))


_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "num_experts", "moe_intermediate_size",
         "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
         "rope_parameters", "sliding_window")


def _cfg_text(cfg: dict) -> str:
    import json

    return json.dumps({k: cfg[k] for k in _KEYS}, sort_keys=True)


def _f32(tree: dict, prefix: str = "") -> dict:
    return {k[len(prefix):]: v.astype(jnp.float32) for k, v in tree.items()}


def forward_hidden(make, cfg: dict, sequences: List, precision: str):
    """The final hidden states [S_padded, e] of every sequence (1-D id
    arrays), the weights made group by group through ``make(table)``."""
    text = _cfg_text(cfg)
    with jax.default_matmul_precision("highest"):
        emb = _f32(make(embed_table(cfg)))["embed_tokens/weight"]
        hidden = []
        for ids in sequences:
            n = -(-len(ids) // PAD_TO) * PAD_TO if len(ids) > Q_BLOCK \
                else len(ids)
            padded = np.zeros((n,), np.int32)
            padded[:len(ids)] = ids
            hidden.append(emb[padded])
        del emb
        for n in range(cfg["num_hidden_layers"]):
            p = _f32(make(layer_table(cfg, n)), f"layer_{n}/")
            fn = _jitted("layer", text, cfg["layer_types"][n], precision)
            hidden = [fn(x, p) for x in hidden]
            jax.block_until_ready(hidden)
            del p
    return hidden


def logits_at(make, cfg: dict, sequences: List, positions: List,
              precision: str = "float32") -> List:
    """Per sequence the logits [K, V] of the next token at its
    ``positions`` [K]."""
    hidden = forward_hidden(make, cfg, sequences, precision)
    with jax.default_matmul_precision("highest"):
        p = _f32(make(head_table(cfg)))
        fn = _jitted("head", _cfg_text(cfg), "", precision)
        return [fn(x[jnp.asarray(pos)], p)
                for x, pos in zip(hidden, positions)]


def mean_gap(make, samples, cfg: dict, *, precision: str = "float32",
             reference_logits: List = None) -> dict:
    """``samples`` is a list of (prompt, served tokens).  ``gap`` is the
    MEAN, over all served tokens, of how far the served token's float32
    logit lies below the float32 best; ``widest`` and ``where`` the worst
    token's.  With another ``precision`` (one of :data:`VARIANTS`) the token
    judged is the one that variant puts first, not the served one.  The
    mean and not the widest, for ``references/glm4_moe_lite.mean_gap``'s
    reason: a routed model is discontinuous in its router, here with eight
    choices a token in every layer.  ``reference_logits`` (an earlier
    call's) saves the float32 pass."""
    if precision not in VARIANTS:
        raise ValueError(f"unknown variant {precision!r}: {VARIANTS}")
    sequences, positions = teacher_forced(samples)
    ref = reference_logits if reference_logits is not None \
        else logits_at(make, cfg, sequences, positions)
    judged = [np.asarray(out) for _, out in samples]
    if precision != "float32":
        judged = [np.asarray(jnp.argmax(lg, axis=-1)) for lg in
                  logits_at(make, cfg, sequences, positions, precision)]
    gaps = token_gaps(ref, judged)
    worst, where = 0.0, None
    for r, g in enumerate(gaps):
        if g.max() > worst:
            worst, where = float(g.max()), (r, int(g.argmax()))
    flat = np.concatenate(gaps)
    return {"gap": float(flat.mean()), "widest": worst, "where": where,
            "tokens": len(flat), "token_gaps": gaps,
            "reference_logits": ref}
