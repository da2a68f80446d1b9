"""Plain reference of the ``lfm2_moe`` decoder's forward pass: float32
``jax.numpy``, no kernels, no cache, no batching of requests, nothing
imported from the program, weights made from the seed group by group.

The equations (the public ``LFM2-24B-A2B`` config, HF
``Lfm2MoeForCausalLM``). Pre-norm residual blocks ``h = x + Mix_i(N(x))``,
``out = h + FFN_i(N(h))``, ``N`` an RMSNorm whose weight is the effective
scale; a final RMSNorm (HF's ``embedding_norm``), then the head TIED to the
embedding. Layer ``i`` is ``layer_types[i]``; its FFN is a dense SwiGLU of
``intermediate_size`` below ``num_dense_layers`` and routed experts above.

- Conv layer (HF ``Lfm2MoeShortConv``): ``[B | C | x] = n W_in^T``, ``u =
  B x``, ``v_t = sum_j w_j u_{t - L + 1 + j}`` written as ``L`` SHIFTED SUMS
  (zeros before the sequence; HF's ``Conv1d`` with ``L - 1`` of padding,
  cut to the sequence), ``y = C v``, ``W_out``.
- Attention layer (HF ``Lfm2MoeAttention``): ``q``, ``k``, ``v`` in heads,
  RMSNorm of ``q`` and ``k`` per head, RoPE (tables written out here)
  rotate-half over the whole head, scores at ``head_dim^-0.5`` under a
  DENSE causal mask, GQA, ``out_proj``.
- Routed layer (HF ``Lfm2MoeSparseMoeBlock`` with ``use_expert_bias``):
  ``s = sigmoid(n W_g^T)``; the ``num_experts_per_tok`` largest of ``s +
  b`` are chosen; their weights are ``s`` WITHOUT ``b``, over their sum plus
  1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``; every expert
  runs over every token and the unchosen are weighted 0.

Departures from HF, each the program's too: everything is float32 here
(HF computes in the model's dtype; the program keeps ``u`` and the window in
bfloat16 and sums in float32); norm weights are the effective scale; the
router's bias is a parameter made from the seed and not a zero buffer.
Weights are held (out, in) like the program's linears, the routed experts
stacked (experts, in, out), the taps ``(L, hidden)``.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.glm4_moe_lite import teacher_forced, token_gaps
from benchmark.references.precision import MATMULS

Q_BLOCK = 512           # queries per block of the attention
PAD_TO = 2048           # sequences are padded to a multiple (causal: free)
ROUTER_EPS = 1e-6       # HF's renormalisation epsilon

#: what the comparison can put in the reference's place (``judge``'s
#: ``precision``): the float8 control, and faults of the program's own: the
#: conv layers' window zeroed where admission hands over to decode, the
#: router's expert bias left out of the choice
VARIANTS = ("float32", "fp8", "window_zeroed", "no_expert_bias")

CONV, FULL = "conv", "full_attention"


def held_dtype(cfg: dict):
    return jnp.dtype(cfg.get("param_dtype", "bfloat16"))


def layer_kind(cfg: dict, n: int) -> str:
    return cfg["layer_types"][n]


def is_dense(cfg: dict, n: int) -> bool:
    return n < cfg["num_dense_layers"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_table(cfg: dict, n: int) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of layer ``n``, named as the program's
    parameter tree names them."""
    e = cfg["hidden_size"]
    bf = held_dtype(cfg)
    p = f"layer_{n}"
    t = {f"{p}/operator_norm/weight": ((e,), bf),
         f"{p}/ffn_norm/weight": ((e,), bf)}
    if layer_kind(cfg, n) == CONV:
        t.update({
            f"{p}/conv/in_proj/weight": ((3 * e, e), bf),
            f"{p}/conv/conv_weight": ((cfg["conv_L_cache"], e), bf),
            f"{p}/conv/out_proj/weight": ((e, e), bf)})
    else:
        d, h, kv = (head_dim(cfg), cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
        t.update({
            f"{p}/self_attn/q_proj/weight": ((h * d, e), bf),
            f"{p}/self_attn/k_proj/weight": ((kv * d, e), bf),
            f"{p}/self_attn/v_proj/weight": ((kv * d, e), bf),
            f"{p}/self_attn/q_layernorm/weight": ((d,), bf),
            f"{p}/self_attn/k_layernorm/weight": ((d,), bf),
            f"{p}/self_attn/out_proj/weight": ((e, h * d), bf)})
    if is_dense(cfg, n):
        w = cfg["intermediate_size"]
        t.update({f"{p}/feed_forward/gate_proj/weight": ((w, e), bf),
                  f"{p}/feed_forward/up_proj/weight": ((w, e), bf),
                  f"{p}/feed_forward/down_proj/weight": ((e, w), bf)})
    else:
        x, m = cfg["num_experts"], cfg["moe_intermediate_size"]
        t.update({
            f"{p}/moe/router/weight": ((x, e), bf),
            f"{p}/moe/router/e_score_correction_bias": ((x,), bf),
            f"{p}/moe/experts/gate_proj": ((x, e, m), bf),
            f"{p}/moe/experts/up_proj": ((x, e, m), bf),
            f"{p}/moe/experts/down_proj": ((x, m, e), bf)})
    return t


def embed_table(cfg: dict) -> Dict[str, tuple]:
    return {"embed_tokens/weight":
            ((cfg["vocab_size"], cfg["hidden_size"]), held_dtype(cfg))}


def head_table(cfg: dict) -> Dict[str, tuple]:
    """The final norm and the head, which is the embedding."""
    return dict(embed_table(cfg), **{
        "embedding_norm/weight": ((cfg["hidden_size"],), held_dtype(cfg))})


def param_table(cfg: dict) -> Dict[str, tuple]:
    """The whole tree's table (the tests' small sizes make it at once)."""
    t = dict(embed_table(cfg))
    for n in range(cfg["num_hidden_layers"]):
        t.update(layer_table(cfg, n))
    t.update(head_table(cfg))
    return t


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def short_conv(x, p, cfg: dict, mm, variant: str, handover):
    """The gated short convolution over one sequence ``x`` [S, e]."""
    s, e = x.shape
    taps = cfg["conv_L_cache"]
    bcx = mm(x, p["conv/in_proj/weight"].T)
    b, c, xx = bcx[:, :e], bcx[:, e:2 * e], bcx[:, 2 * e:]
    u = b * xx
    padded = jnp.concatenate([jnp.zeros((taps - 1, e), u.dtype), u])
    w = p["conv/conv_weight"]
    pos = jnp.arange(s)
    v = 0.0
    for j in range(taps):
        term = w[j] * padded[j:j + s]
        if variant == "window_zeroed":
            # decode from the hand-over on reads no value of u from before
            # it: the window admission handed over is zeros
            src = pos - (taps - 1) + j
            term = jnp.where(((pos >= handover) & (src < handover))[:, None],
                             0.0, term)
        v = v + term
    return mm(c * v, p["conv/out_proj/weight"].T)


def _rope(x, positions, theta: float):
    """Rotate-half RoPE over the whole head of ``x`` [S, heads, d]."""
    d = x.shape[-1]
    freqs = (1.0 / np.float32(theta) ** (
        np.arange(0, d, 2, dtype=np.float32) / np.float32(d))
    ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(x, p, cfg: dict, mm):
    """GQA over one sequence ``x`` [S, e], queries in blocks, dense causal
    mask."""
    s = x.shape[0]
    d, h, kv = (head_dim(cfg), cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    rep, eps = h // kv, cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    pos = jnp.arange(s, dtype=jnp.int32)
    q = mm(x, p["self_attn/q_proj/weight"].T).reshape(s, h, d)
    k = mm(x, p["self_attn/k_proj/weight"].T).reshape(s, kv, d)
    v = mm(x, p["self_attn/v_proj/weight"].T).reshape(s, kv, d)
    q = _rope(_rms_norm(q, p["self_attn/q_layernorm/weight"], eps), pos,
              theta)
    k = _rope(_rms_norm(k, p["self_attn/k_layernorm/weight"], eps), pos,
              theta)
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
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return mm(probs, v_t).transpose(2, 0, 1, 3).reshape(rows, h * d)

    ctx = jax.lax.map(block, (q.reshape(blocks, rows, h, d),
                              pos.reshape(blocks, rows))).reshape(s, h * d)
    return mm(ctx, p["self_attn/out_proj/weight"].T)


def route(x, p, cfg: dict, mm, variant: str):
    """``[S, experts]`` weights of the routed experts, 0 where not chosen."""
    scores = jax.nn.sigmoid(mm(x, p["moe/router/weight"].T))
    chosen_by = scores
    if variant != "no_expert_bias":
        chosen_by = scores + p["moe/router/e_score_correction_bias"]
    _, idx = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTER_EPS)
    w = w * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(w)


def _swiglu(mm, x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate.T)) * mm(x, up.T), down.T)


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


def layer(x, p, handover, cfg: dict, kind: str, dense: bool,
          precision: str = "float32"):
    """One block over one sequence ``x`` [S, e]; ``p`` holds the layer's
    leaves without the ``layer_n/`` prefix, in float32; ``handover`` is the
    position of the first decoded token (read by ``window_zeroed``
    alone)."""
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    eps = cfg["norm_eps"]
    n = _rms_norm(x, p["operator_norm/weight"], eps)
    x = x + (short_conv(n, p, cfg, mm, precision, handover) if kind == CONV
             else attention(n, p, cfg, mm))
    hn = _rms_norm(x, p["ffn_norm/weight"], eps)
    if dense:
        return x + _swiglu(mm, hn, p["feed_forward/gate_proj/weight"],
                           p["feed_forward/up_proj/weight"],
                           p["feed_forward/down_proj/weight"])
    return x + expert_layer(hn, p, cfg, mm, precision)


def head(x, p, cfg: dict, precision: str = "float32"):
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    return mm(_rms_norm(x, p["embedding_norm/weight"], cfg["norm_eps"]),
              p["embed_tokens/weight"].T)


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "rope_parameters", "conv_L_cache", "num_experts",
         "moe_intermediate_size", "intermediate_size", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "norm_eps")


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_text: str, kind: str, dense: bool,
            precision: str):
    cfg = json.loads(cfg_text)
    if what == "layer":
        return jax.jit(functools.partial(layer, cfg=cfg, kind=kind,
                                         dense=dense, precision=precision))
    return jax.jit(functools.partial(head, cfg=cfg, precision=precision))


def _cfg_text(cfg: dict) -> str:
    return json.dumps({k: cfg[k] for k in _KEYS}, sort_keys=True)


def _f32(tree: dict, prefix: str = "") -> dict:
    return {k[len(prefix):]: v.astype(jnp.float32) for k, v in tree.items()}


def forward_hidden(make, cfg: dict, sequences: List, precision: str,
                   handovers: List = None):
    """The final hidden states [S_padded, e] of every sequence (1-D id
    arrays), the weights made group by group through ``make(table)``."""
    text = _cfg_text(cfg)
    handovers = handovers or [-1] * len(sequences)
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
            fn = _jitted("layer", text, layer_kind(cfg, n), is_dense(cfg, n),
                         precision)
            hidden = [fn(x, p, jnp.int32(at))
                      for x, at in zip(hidden, handovers)]
            jax.block_until_ready(hidden)
            del p
    return hidden


def logits_at(make, cfg: dict, sequences: List, positions: List,
              precision: str = "float32") -> List:
    """Per sequence the logits [K, V] of the next token at its
    ``positions`` [K]. (``window_zeroed`` takes the hand-over to be the
    position after the first of them: the first decoded token's.)"""
    handovers = [int(pos[0]) + 1 for pos in positions]
    hidden = forward_hidden(make, cfg, sequences, precision, handovers)
    with jax.default_matmul_precision("highest"):
        p = _f32(make(head_table(cfg)))
        fn = _jitted("head", _cfg_text(cfg), "", False, precision)
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
    reason: a routed model is discontinuous in its router, here with four
    choices a token in eight layers.  ``reference_logits`` (an earlier
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
