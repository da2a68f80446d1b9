"""Plain reference of the ``glm4_moe_lite`` decoder's forward pass: float32
``jax.numpy``, no kernels, no cache, no batching of requests, nothing
imported from the program, weights made from the seed layer by layer (the
float32 tree of the cell's configuration is 15.6 GB and never exists whole).

The equations (ISSUE 30, part A; DeepSeek-V2's MLA and DeepSeek-V3's
``noaux_tc`` router as the public ``Glm4MoeLiteForCausalLM`` config states
them).  Pre-norm residual blocks, RMSNorm, SiLU, no biases, untied head.

- MLA, in the EXPANDED form only: ``c_q = RMSNorm(x W_dq)``, ``q = c_q
  W_uq`` in heads of ``[q_nope; q_rope]``; ``[c_kv; k_r] = x W_dkv``, ``c_kv =
  RMSNorm(c_kv)``, ``k_rope = RoPE(k_r)`` (one for all heads), ``[k_nope_h;
  v_h] = c_kv W_ukv,h``; scores ``(q_nope . k_nope + q_rope . k_rope) /
  sqrt(qk_nope + qk_rope)``, causal softmax, ``out = concat_h(P_h v_h) W_o``.
  The program's decode path absorbs ``W_uk`` into the query and ``W_uv`` into
  the output and never expands a cache entry: it is checked against
  mathematics it does not share.
- Layers below ``first_k_dense_replace``: one SwiGLU of ``intermediate_size``.
- Expert layers: ``s = sigmoid(x W_g^T)``; the ``num_experts_per_tok``
  largest of ``s + b`` are chosen (``n_group = topk_group = 1``: no group
  step); their weights are ``s`` WITHOUT ``b``, normalised to sum 1 (+1e-20)
  and scaled by ``routed_scaling_factor``; ``y = sum_i w_i E_i(x) +
  E_shared(x)``.  Every expert runs over every token here and the unchosen
  ones are weighted 0: no token is dropped because none is ever dispatched.
- RoPE pairs dims ``(i, i + d/2)`` (rotate-half), ``theta`` from the config.

Weights are held (out, in) like the program's linears, the routed experts
stacked (experts, in, out).  The table states the configuration's
``param_dtype`` (bfloat16: the model is published in it), so the reference
computes in float32 on the same rounded values the program holds.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmark.references.precision import MATMULS

Q_BLOCK = 512           # queries per block of the attention
PAD_TO = 2048           # sequences are padded to a multiple (causal: free)

#: what the comparison can put in the reference's place (``judge``'s
#: ``precision``): the float8 control, and two faults of the program's own
VARIANTS = ("float32", "fp8", "no_selection_bias", "k_rope_unrotated")


def dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return {"e": cfg["hidden_size"], "h": h, "nope": nope, "rope": rope,
            "vd": vd, "qk": nope + rope, "q_rank": cfg["q_lora_rank"],
            "kv_rank": cfg["kv_lora_rank"],
            "experts": cfg["n_routed_experts"],
            "moe": cfg["moe_intermediate_size"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "dense": cfg["intermediate_size"]}


def held_dtype(cfg: dict):
    """The dtype the weights are published and held in (bfloat16 unless the
    configuration says otherwise): the reference computes in float32 on the
    values as that dtype rounds them."""
    return jnp.dtype(cfg.get("param_dtype", "bfloat16"))


def is_dense(cfg: dict, n: int) -> bool:
    return n < cfg["first_k_dense_replace"]


def layer_table(cfg: dict, n: int) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of layer ``n``, named as the program's
    parameter tree names them."""
    d = dims(cfg)
    bf = held_dtype(cfg)
    p = f"layer_{n}"
    t = {f"{p}/input_norm/weight": ((d["e"],), bf),
         f"{p}/post_norm/weight": ((d["e"],), bf),
         f"{p}/attn/q_a_proj/weight": ((d["q_rank"], d["e"]), bf),
         f"{p}/attn/q_a_norm/weight": ((d["q_rank"],), bf),
         f"{p}/attn/q_b_proj/weight": ((d["h"] * d["qk"], d["q_rank"]), bf),
         f"{p}/attn/kv_a_proj/weight": ((d["kv_rank"] + d["rope"], d["e"]), bf),
         f"{p}/attn/kv_a_norm/weight": ((d["kv_rank"],), bf),
         f"{p}/attn/kv_b_proj/weight": ((d["h"] * (d["nope"] + d["vd"]),
                                    d["kv_rank"]), bf),
         f"{p}/attn/o_proj/weight": ((d["e"], d["h"] * d["vd"]), bf)}

    def swiglu(prefix, width):
        t[f"{prefix}/gate_proj/weight"] = ((width, d["e"]), bf)
        t[f"{prefix}/up_proj/weight"] = ((width, d["e"]), bf)
        t[f"{prefix}/down_proj/weight"] = ((d["e"], width), bf)

    if is_dense(cfg, n):
        swiglu(f"{p}/mlp", d["dense"])
    else:
        swiglu(f"{p}/moe/shared", d["shared"])
        x, m = d["experts"], d["moe"]
        t[f"{p}/moe/router/weight"] = ((x, d["e"]), bf)
        t[f"{p}/moe/router/e_score_correction_bias"] = ((x,), bf)
        t[f"{p}/moe/experts/gate_proj"] = ((x, d["e"], m), bf)
        t[f"{p}/moe/experts/up_proj"] = ((x, d["e"], m), bf)
        t[f"{p}/moe/experts/down_proj"] = ((x, m, d["e"]), bf)
    return t


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


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotate-half RoPE over the last dim of ``x`` [S, ..., d]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _swiglu(mm, x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate.T)) * mm(x, up.T), down.T)


def attention(x, p, cfg: dict, mm, variant: str):
    """MLA over one sequence ``x`` [S, e], expanded, queries in blocks."""
    d = dims(cfg)
    s = x.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s, dtype=jnp.int32)
    c_q = _rms_norm(mm(x, p["attn/q_a_proj/weight"].T), p["attn/q_a_norm/weight"], eps)
    q = mm(c_q, p["attn/q_b_proj/weight"].T).reshape(s, d["h"], d["qk"])
    q_nope, q_rope = q[..., :d["nope"]], _rope(q[..., d["nope"]:], pos, theta)
    ckv = mm(x, p["attn/kv_a_proj/weight"].T)
    c_kv = _rms_norm(ckv[:, :d["kv_rank"]], p["attn/kv_a_norm/weight"], eps)
    k_rope = ckv[:, d["kv_rank"]:]
    if variant != "k_rope_unrotated":
        k_rope = _rope(k_rope, pos, theta)
    kv = mm(c_kv, p["attn/kv_b_proj/weight"].T).reshape(
        s, d["h"], d["nope"] + d["vd"])
    k_nope, v = kv[..., :d["nope"]], kv[..., d["nope"]:]
    scale = 1.0 / jnp.sqrt(jnp.float32(d["qk"]))
    blocks = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    rows = s // blocks

    def block(args):
        qn, qr, qpos = args                 # [rows, h, .], [rows]
        scores = (mm(qn.transpose(1, 0, 2), k_nope.transpose(1, 2, 0))
                  + mm(qr.transpose(1, 0, 2), k_rope.T[None])) * scale
        mask = pos[None, None, :] <= qpos[None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return mm(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)

    ctx = jax.lax.map(block, (
        q_nope.reshape(blocks, rows, d["h"], d["nope"]),
        q_rope.reshape(blocks, rows, d["h"], d["rope"]),
        pos.reshape(blocks, rows)))
    return mm(ctx.reshape(s, d["h"] * d["vd"]), p["attn/o_proj/weight"].T)


def route(x, p, cfg: dict, mm, variant: str):
    """``[S, experts]`` weights of the routed experts, 0 where not chosen."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm(x, p["moe/router/weight"].T))
    chosen_by = scores
    if variant != "no_selection_bias":
        chosen_by = scores + p["moe/router/e_score_correction_bias"]
    _, idx = jax.lax.top_k(chosen_by, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(w)


def expert_layer(x, p, cfg: dict, mm, variant: str):
    weights = route(x, p, cfg, mm, variant)

    def one(y, e):
        gate, up, down, w = e
        return y + w[:, None] * mm(
            jax.nn.silu(mm(x, gate)) * mm(x, up), down), None

    y = _swiglu(mm, x, p["moe/shared/gate_proj/weight"],
                p["moe/shared/up_proj/weight"],
                p["moe/shared/down_proj/weight"])
    y, _ = jax.lax.scan(one, y, (
        p["moe/experts/gate_proj"], p["moe/experts/up_proj"],
        p["moe/experts/down_proj"], weights.T))
    return y


def layer(x, p, cfg: dict, dense: bool, precision: str = "float32"):
    """One block over one sequence ``x`` [S, e]; ``p`` holds the layer's
    leaves without the ``layer_n/`` prefix, in float32."""
    variant = precision
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms_norm(x, p["input_norm/weight"], eps), p, cfg, mm,
                      variant)
    hn = _rms_norm(x, p["post_norm/weight"], eps)
    if dense:
        return x + _swiglu(mm, hn, p["mlp/gate_proj/weight"],
                           p["mlp/up_proj/weight"],
                           p["mlp/down_proj/weight"])
    return x + expert_layer(hn, p, cfg, mm, variant)


def head(x, p, cfg: dict, precision: str = "float32"):
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    return mm(_rms_norm(x, p["final_norm/weight"], cfg["rms_norm_eps"]),
              p["lm_head/weight"].T)


_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
         "n_routed_experts", "n_shared_experts", "moe_intermediate_size",
         "intermediate_size", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "rms_norm_eps", "rope_theta")


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_items: tuple, dense: bool, precision: str):
    cfg = dict(cfg_items)
    if what == "layer":
        return jax.jit(functools.partial(layer, cfg=cfg, dense=dense,
                                         precision=precision))
    return jax.jit(functools.partial(head, cfg=cfg, precision=precision))


def _f32(tree: dict, prefix: str = "") -> dict:
    return {k[len(prefix):]: v.astype(jnp.float32) for k, v in tree.items()}


def forward_hidden(make, cfg: dict, sequences: List, precision: str):
    """The final hidden states [S_padded, e] of every sequence (1-D id
    arrays), the weights made group by group through ``make(table)``."""
    import numpy as np

    items = tuple((k, cfg[k]) for k in _KEYS)
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
            fn = _jitted("layer", items, is_dense(cfg, n), precision)
            hidden = [fn(x, p) for x in hidden]
            jax.block_until_ready(hidden)
            del p
    return hidden


def logits_at(make, cfg: dict, sequences: List, positions: List,
              precision: str = "float32") -> List:
    """Per sequence the logits [K, V] of the next token at its
    ``positions`` [K]."""
    items = tuple((k, cfg[k]) for k in _KEYS)
    hidden = forward_hidden(make, cfg, sequences, precision)
    with jax.default_matmul_precision("highest"):
        p = _f32(make(head_table(cfg)))
        fn = _jitted("head", items, False, precision)
        return [fn(x[jnp.asarray(pos)], p)
                for x, pos in zip(hidden, positions)]


def teacher_forced(samples):
    """(sequences, positions) that teacher-force ``samples`` = [(prompt,
    served tokens)]: the logits at ``positions`` are those the served
    tokens were chosen from."""
    import numpy as np

    return ([np.concatenate([prompt, out[:-1]]) for prompt, out in samples],
            [len(prompt) - 1 + np.arange(len(out)) for prompt, out in samples])


def token_gaps(reference_logits: List, judged: List) -> List:
    """Per sample and position: how far the reference's logit of the judged
    token lies below the reference's best."""
    import numpy as np

    out = []
    for lg, tok in zip(reference_logits, judged):
        lg, tok = np.asarray(lg), np.asarray(tok)
        out.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
    return out


def mean_gap(make, samples, cfg: dict, *, precision: str = "float32",
             reference_logits: List = None) -> dict:
    """``samples`` is a list of (prompt, served tokens).  ``gap`` is the
    MEAN, over all served tokens, of how far the served token's float32
    logit lies below the float32 best; ``widest`` and ``where`` the worst
    token's.  With another ``precision`` (one of :data:`VARIANTS`) the token
    judged is the one that variant puts first, not the served one.

    The mean and not the widest: a routed model is discontinuous in its
    router.  Rounding moves a score across the top-k boundary for some
    tokens in some layer, the token then runs through another expert, and
    with seeded (untrained) experts that is another function: on the chip
    the bfloat16 program's WIDEST gap reads 1.05-1.58 and the float8
    control's 1.5-1.8, both near the spread of the logits themselves, while
    nine tokens in ten are served exactly as the reference would and the
    means read 0.02-0.03 against 0.25 (PERF.md section 6).
    ``reference_logits`` (an earlier call's) saves the float32 pass."""
    import numpy as np

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
