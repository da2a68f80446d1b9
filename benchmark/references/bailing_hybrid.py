"""Plain reference of the ``bailing_hybrid`` decoder's forward pass (Ling):
float32 ``jax.numpy``, no kernels, no cache, no batching of requests,
nothing imported from the program, weights made from the seed layer by
layer.

The equations (the public ``Ling-3.0-flash`` config). Pre-norm
residual blocks ``x -> x + Mix_n(N(x))``, then ``-> h + FFN_n(N(h))``,
``N`` an RMSNorm whose weight is the effective scale; final norm, untied
head. Held layer ``n`` is published layer ``first_layer + n``, which is MLA
where ``(first_layer + n + 1) % layer_group_size == 0`` and KDA otherwise;
it is dense where ``n < first_k_dense_replace``.

- KDA layer (Kimi delta attention): ``q, k, v = n W_{q,k,v}^T``; the three
  causal depthwise convolutions of ``short_conv_kernel_size`` taps written
  as SHIFTED SUMS, then SiLU; ``q``, ``k`` L2-normalised per head, ``q``
  times ``dk^-1/2``; ``beta = sigmoid(n W_b^T)``; per key channel ``g =
  kda_lower_bound sigmoid(exp(A_log_h) (n W_f^T + dt_bias +
  dt_bias_center))``; then TOKEN BY TOKEN under ``lax.scan``: ``S <-
  diag(exp g_t) S``, ``u = S^T k_t``, ``S <- S + k_t (beta_t (v_t -
  u))^T``, ``o_t = S^T q_t`` (the program's chunked form shares no algebra
  with it); ``o <- w o / rms(o) sigmoid(n W_go^T)`` per head; ``W_o``.
- MLA layer, EXPANDED: ``q = n W_q^T`` in heads of 192, RMSNorm of each
  head's query; ``[c_kv; k_r] = n W_dkv^T``, ``c_kv = RMSNorm(c_kv)``; RoPE
  on the 64 rotated dims of ``q`` and of ``k_r`` (one for all heads) as
  ROTATIONS OF ADJACENT PAIRS ``(2i, 2i + 1)`` at frequency ``i`` (the
  program gathers them into halves and rotates half: the same scores);
  ``[k_nope_h; v_h] = c_kv W_ukv,h``; scores over 192 dims at
  ``192^-1/2`` under a dense causal mask; each head's context times
  ``sigmoid(n . w_h)``; ``W_o``.
- Routed layer: ``s = sigmoid(n' W_r^T)`` over ALL ``router_experts``;
  ``s + b`` in ``n_group`` groups of consecutive experts, a group scored by
  the sum of its two largest, the ``topk_group`` best kept, the
  ``num_experts_per_tok`` largest chosen inside them; weights ``s``
  renormalised over the chosen (+1e-20) times ``routed_scaling_factor``; of
  them only the experts this chip holds (``num_experts`` from
  ``first_expert``) are computed, every held expert over every token and
  the unchosen weighted 0; plus the UNGATED shared SwiGLU.

Weights are held (out, in) like the program's linears, the routed experts
stacked (experts, in, out).
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

#: what the comparison can put in the reference's place (``judge``'s
#: ``precision``): the float8 control, and faults of the program's own: the
#: KDA layers' recurrent state zeroed where admission hands over to decode,
#: each head's per-channel decay replaced by its mean over the key channels
#: (a scalar gate: Gated DeltaNet's rule), and the experts chosen over all
#: 512 without the group step
VARIANTS = ("float32", "fp8", "state_zeroed", "scalar_decay",
            "no_group_limit")

KDA, MLA = "kda", "mla"


def held_dtype(cfg: dict):
    return jnp.dtype(cfg.get("param_dtype", "bfloat16"))


def layer_kind(cfg: dict, n: int) -> str:
    published = cfg.get("first_layer", 0) + n
    return MLA if (published + 1) % cfg["layer_group_size"] == 0 else KDA


def is_dense(cfg: dict, n: int) -> bool:
    return n < cfg["first_k_dense_replace"]


def router_experts(cfg: dict) -> int:
    """The router's width: the published count of experts, of which the
    file's ``num_experts`` are held."""
    return cfg.get("router_experts", cfg["num_experts"])


def layer_table(cfg: dict, n: int) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of layer ``n``, named as the program's
    parameter tree names them."""
    e, h, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    bf = held_dtype(cfg)
    p = f"layer_{n}"
    t = {f"{p}/input_norm/weight": ((e,), bf),
         f"{p}/post_norm/weight": ((e,), bf)}
    if layer_kind(cfg, n) == KDA:
        w = h * d
        t.update({f"{p}/mixer/{m}_proj/weight": ((w, e), bf)
                  for m in ("q", "k", "v", "f", "g")})
        t.update({
            f"{p}/mixer/conv_weight":
            ((3 * w, cfg["short_conv_kernel_size"]), bf),
            f"{p}/mixer/b_proj/weight": ((h, e), bf),
            f"{p}/mixer/A_log": ((h,), bf),
            f"{p}/mixer/dt_bias": ((w,), bf),
            f"{p}/mixer/norm_weight": ((d,), bf),
            f"{p}/mixer/o_proj/weight": ((e, w), bf)})
    else:
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        rank = cfg["kv_lora_rank"]
        t.update({
            f"{p}/attn/q_proj/weight": ((h * (nope + rope), e), bf),
            f"{p}/attn/q_norm/weight": ((nope + rope,), bf),
            f"{p}/attn/kv_a_proj/weight": ((rank + rope, e), bf),
            f"{p}/attn/kv_a_norm/weight": ((rank,), bf),
            f"{p}/attn/kv_b_proj/weight": ((h * (nope + vd), rank), bf),
            f"{p}/attn/g_proj/weight": ((h, e), bf),
            f"{p}/attn/o_proj/weight": ((e, h * vd), bf)})

    def swiglu(prefix, width):
        t[f"{prefix}/gate_proj/weight"] = ((width, e), bf)
        t[f"{prefix}/up_proj/weight"] = ((width, e), bf)
        t[f"{prefix}/down_proj/weight"] = ((e, width), bf)

    if is_dense(cfg, n):
        swiglu(f"{p}/mlp", cfg["intermediate_size"])
    else:
        m, x = cfg["moe_intermediate_size"], cfg["num_experts"]
        swiglu(f"{p}/moe/shared", m * cfg["num_shared_experts"])
        t.update({
            f"{p}/moe/router/weight": ((router_experts(cfg), e), bf),
            f"{p}/moe/router/e_score_correction_bias":
            ((router_experts(cfg),), bf),
            f"{p}/moe/experts/gate_proj": ((x, e, m), bf),
            f"{p}/moe/experts/up_proj": ((x, e, m), bf),
            f"{p}/moe/experts/down_proj": ((x, m, e), bf)})
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


def _l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _swiglu(mm, x, p, prefix):
    return mm(jax.nn.silu(mm(x, p[f"{prefix}/gate_proj/weight"].T))
              * mm(x, p[f"{prefix}/up_proj/weight"].T),
              p[f"{prefix}/down_proj/weight"].T)


def delta_rule(q, k, v, g, beta, handover, variant: str):
    """The recurrence over one sequence, a token a step: ``q``, ``k``, ``g``
    [S, H, dk], ``v`` [S, H, dv], ``beta`` [S, H]; ``o`` [S, H, dv]."""
    s, h, dv = v.shape
    if variant == "scalar_decay":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)

    def one(state, x):
        t, q_t, k_t, v_t, g_t, b_t = x
        if variant == "state_zeroed":
            state = jnp.where(t == handover, 0.0, state)
        state = state * jnp.exp(g_t)[:, :, None]
        u = (state * k_t[:, :, None]).sum(1)                     # [H, dv]
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - u))[:, None]
        return state, (state * q_t[:, :, None]).sum(1)

    state = jnp.zeros((h, q.shape[-1], dv), jnp.float32)
    _, o = jax.lax.scan(one, state, (jnp.arange(s), q, k, v, g, beta))
    return o


def kda(x, p, cfg: dict, mm, variant: str, handover):
    """The KDA mixer over one sequence ``x`` [S, e]."""
    s = x.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    w, taps = h * d, cfg["short_conv_kernel_size"]
    mixed = jnp.concatenate([mm(x, p[f"mixer/{m}_proj/weight"].T)
                             for m in "qkv"], -1)
    # each channel's causal convolution as shifted sums: output t is
    # sum_j w[:, j] x[t - (taps - 1) + j], zeros before the sequence
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3 * w)), mixed])
    conv = jax.nn.silu(sum(padded[j:j + s] * p["mixer/conv_weight"][:, j]
                           for j in range(taps)))
    q = _l2_norm(conv[:, :w].reshape(s, h, d)) * d ** -0.5
    k = _l2_norm(conv[:, w:2 * w].reshape(s, h, d))
    v = conv[:, 2 * w:].reshape(s, h, d)
    beta = jax.nn.sigmoid(mm(x, p["mixer/b_proj/weight"].T))
    f = mm(x, p["mixer/f_proj/weight"].T) + p["mixer/dt_bias"] \
        + cfg.get("dt_bias_center", 0.0)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["mixer/A_log"])[:, None] * f.reshape(s, h, d))
    o = delta_rule(q, k, v, g, beta, handover, variant)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = o * p["mixer/norm_weight"] * jax.nn.sigmoid(
        mm(x, p["mixer/g_proj/weight"].T).reshape(s, h, d))
    return mm(o.reshape(s, w), p["mixer/o_proj/weight"].T)


def _rope_pairs(x, positions, theta: float):
    """RoPE on adjacent pairs: dims ``(2i, 2i + 1)`` of ``x`` [S, ..., d]
    turned by ``positions x theta^(-2i / d)``."""
    d = x.shape[-1]
    freqs = (1.0 / np.float32(theta) ** (
        np.arange(0, d, 2, dtype=np.float32) / np.float32(d)))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def mla(x, p, cfg: dict, mm):
    """MLA over one sequence ``x`` [S, e], expanded, queries in blocks."""
    s = x.shape[0]
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s, dtype=jnp.int32)
    q = _rms_norm(mm(x, p["attn/q_proj/weight"].T).reshape(s, h, nope + rope),
                  p["attn/q_norm/weight"], eps)
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], pos, theta)
    ckv = mm(x, p["attn/kv_a_proj/weight"].T)
    c_kv = _rms_norm(ckv[:, :rank], p["attn/kv_a_norm/weight"], eps)
    k_rope = _rope_pairs(ckv[:, rank:], pos, theta)
    kv = mm(c_kv, p["attn/kv_b_proj/weight"].T).reshape(s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))
    blocks = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    rows = s // blocks

    def block(args):
        qn, qr, qpos = args                 # [rows, h, .], [rows]
        scores = (mm(qn.transpose(1, 0, 2), k_nope.transpose(1, 2, 0))
                  + mm(qr.transpose(1, 0, 2), k_rope.T[None])) * scale
        mask = pos[None, None, :] <= qpos[None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return mm(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2)

    ctx = jax.lax.map(block, (q_nope.reshape(blocks, rows, h, nope),
                              q_rope.reshape(blocks, rows, h, rope),
                              pos.reshape(blocks, rows))).reshape(s, h, vd)
    ctx = ctx * jax.nn.sigmoid(mm(x, p["attn/g_proj/weight"].T))[..., None]
    return mm(ctx.reshape(s, h * vd), p["attn/o_proj/weight"].T)


def route(x, p, cfg: dict, mm, variant: str):
    """``[S, held experts]`` weights of the experts this chip holds, 0
    where not chosen: the groups and the top k run over ALL the router's
    experts."""
    scores = jax.nn.sigmoid(mm(x, p["moe/router/weight"].T))
    choice = scores + p["moe/router/e_score_correction_bias"]
    groups = cfg["n_group"]
    if groups > 1 and variant != "no_group_limit":
        per = choice.reshape(x.shape[0], groups, -1)
        top2 = jnp.sort(per, axis=-1)[..., -2:].sum(-1)          # [S, G]
        rank = jnp.argsort(jnp.argsort(-top2, axis=-1), axis=-1)
        keep = rank < cfg["topk_group"]
        choice = jnp.where(keep[..., None], per, -jnp.inf).reshape(
            choice.shape)
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    full = jnp.zeros_like(scores).at[rows, idx].set(w)
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]
    return full[:, first:first + held]


def expert_layer(x, p, cfg: dict, mm, variant: str):
    weights = route(x, p, cfg, mm, variant)

    def one(y, e):
        gate, up, down, w = e
        return y + w[:, None] * mm(
            jax.nn.silu(mm(x, gate)) * mm(x, up), down), None

    y, _ = jax.lax.scan(one, _swiglu(mm, x, p, "moe/shared"), (
        p["moe/experts/gate_proj"], p["moe/experts/up_proj"],
        p["moe/experts/down_proj"], weights.T))
    return y


def layer(x, p, handover, cfg: dict, kind: str, dense: bool,
          precision: str = "float32"):
    """One block over one sequence ``x`` [S, e]; ``p`` holds the layer's
    leaves without the ``layer_n/`` prefix, in float32; ``handover`` is the
    position of the first decoded token (read by ``state_zeroed`` alone)."""
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    eps = cfg["rms_norm_eps"]
    n = _rms_norm(x, p["input_norm/weight"], eps)
    x = x + (kda(n, p, cfg, mm, precision, handover) if kind == KDA
             else mla(n, p, cfg, mm))
    hn = _rms_norm(x, p["post_norm/weight"], eps)
    return x + (_swiglu(mm, hn, p, "mlp") if dense
                else expert_layer(hn, p, cfg, mm, precision))


def head(x, p, cfg: dict, precision: str = "float32"):
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    return mm(_rms_norm(x, p["final_norm/weight"], cfg["rms_norm_eps"]),
              p["lm_head/weight"].T)


_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "short_conv_kernel_size", "kda_lower_bound", "dt_bias_center",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "kv_lora_rank", "rope_theta", "num_experts", "router_experts",
         "first_expert", "n_group", "topk_group", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps")


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_text: str, kind: str, dense: bool,
            precision: str):
    cfg = json.loads(cfg_text)
    if what == "layer":
        return jax.jit(functools.partial(layer, cfg=cfg, kind=kind,
                                         dense=dense, precision=precision))
    return jax.jit(functools.partial(head, cfg=cfg, precision=precision))


def _cfg_text(cfg: dict) -> str:
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


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
    ``positions`` [K]. (``state_zeroed`` takes the hand-over to be the
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
    reason: a routed model is discontinuous in its router, here with eight
    choices a token in six layers.  ``reference_logits`` (an earlier
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
