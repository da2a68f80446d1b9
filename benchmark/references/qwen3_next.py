"""Plain reference of the ``qwen3_next`` decoder's forward pass: float32
``jax.numpy``, no kernels, no cache, no batching of requests, nothing
imported from the program, weights made from the seed group by group.

The equations (ISSUE 37; the public ``Qwen3-Next-80B-A3B-Instruct`` config).
Pre-norm residual blocks ``x -> x + Mix_i(N(x))``, then ``-> h + MoE(N(h))``,
``N`` an RMSNorm whose weight is the effective scale; final norm, untied
head. Layer ``i`` is ``linear_attention`` unless ``(i + 1) %
full_attention_interval == 0``.

- Linear layer (gated delta rule): ``[q | k | v | z] = n W_qkvz^T``, ``[b |
  a] = n W_ba^T``; ``[q | k | v]`` through a causal depthwise convolution
  written as ``taps`` SHIFTED SUMS, then SiLU; ``q`` and ``k`` in
  ``linear_num_key_heads`` heads, each repeated to its value heads,
  L2-normalised, ``q`` times ``dk^-0.5``; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; then TOKEN BY TOKEN under
  ``lax.scan``: ``S <- exp(g_t) S``, ``u = S^T k_t``, ``S <- S + k_t (beta_t
  (v_t - u))^T``, ``o_t = S^T q_t`` (the program's chunked form shares no
  algebra with it); ``o <- w o / rms(o) silu(z)`` per head; ``W_out``.
- Full layer (gated attention): ``W_q n`` is heads x 2 head_dim, a head's
  first half its query, its second half its gate; RMSNorm of ``q`` and
  ``k`` per head; RoPE (tables written out here) on the first
  ``partial_rotary_factor x head_dim`` dims, rotate-half inside them; scores
  under a DENSE causal mask; ``out = W_o (attn sigmoid(gate))``.
- Routed layer: ``p = softmax(n' W_r^T)`` over ALL ``router_experts``, the
  ``num_experts_per_tok`` largest, renormalised over those; of them only
  the experts this chip holds (``num_experts`` from ``first_expert``) are
  computed, every held expert over every token and the unchosen weighted 0;
  what the absent experts would add is left out, as in the program; plus
  ``sigmoid(n' . w_g) SwiGLU_shared(n')``.

Weights are held (out, in) like the program's linears, the routed experts
stacked (experts, in, out).
"""

from __future__ import annotations

import functools
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
#: decay left out (``g = 0``), the correction left out (``u = 0``: plain
#: gated linear attention), the full layers' output gate left out, the
#: chosen experts' weights renormalised over the HELD ones, the recurrent
#: state zeroed where admission hands over to decode
VARIANTS = ("float32", "fp8", "no_decay", "no_delta", "no_gate",
            "renorm_held", "state_zeroed")

LINEAR, FULL = "linear_attention", "full_attention"


def held_dtype(cfg: dict):
    return jnp.dtype(cfg.get("param_dtype", "bfloat16"))


def layer_kind(cfg: dict, n: int) -> str:
    return FULL if (n + 1) % cfg["full_attention_interval"] == 0 else LINEAR


def router_experts(cfg: dict) -> int:
    """The router's width: the published count of experts, of which the
    file's ``num_experts`` are held."""
    return cfg.get("router_experts", cfg["num_experts"])


def linear_dims(cfg: dict):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv


def layer_table(cfg: dict, n: int) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of layer ``n``, named as the program's
    parameter tree names them."""
    e, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    bf = held_dtype(cfg)
    p = f"layer_{n}"
    t = {f"{p}/input_norm/weight": ((e,), bf),
         f"{p}/post_norm/weight": ((e,), bf)}
    if layer_kind(cfg, n) == LINEAR:
        hk, hv, dk, dv = linear_dims(cfg)
        conv = 2 * hk * dk + hv * dv
        t.update({
            f"{p}/mixer/in_proj_qkvz/weight": ((conv + hv * dv, e), bf),
            f"{p}/mixer/in_proj_ba/weight": ((2 * hv, e), bf),
            f"{p}/mixer/conv_weight":
            ((conv, cfg["linear_conv_kernel_dim"]), bf),
            f"{p}/mixer/A_log": ((hv,), bf),
            f"{p}/mixer/dt_bias": ((hv,), bf),
            f"{p}/mixer/norm_weight": ((dv,), bf),
            f"{p}/mixer/out_proj/weight": ((e, hv * dv), bf)})
    else:
        d, h, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
        t.update({
            f"{p}/attn/q_proj/weight": ((h * 2 * d, e), bf),
            f"{p}/attn/k_proj/weight": ((kv * d, e), bf),
            f"{p}/attn/v_proj/weight": ((kv * d, e), bf),
            f"{p}/attn/q_norm/weight": ((d,), bf),
            f"{p}/attn/k_norm/weight": ((d,), bf),
            f"{p}/attn/o_proj/weight": ((e, h * d), bf)})
    x, ms = cfg["num_experts"], cfg["shared_expert_intermediate_size"]
    t.update({
        f"{p}/moe/router/weight": ((router_experts(cfg), e), bf),
        f"{p}/moe/experts/gate_proj": ((x, e, m), bf),
        f"{p}/moe/experts/up_proj": ((x, e, m), bf),
        f"{p}/moe/experts/down_proj": ((x, m, e), bf),
        f"{p}/moe/shared/gate_proj/weight": ((ms, e), bf),
        f"{p}/moe/shared/up_proj/weight": ((ms, e), bf),
        f"{p}/moe/shared/down_proj/weight": ((e, ms), bf),
        f"{p}/moe/shared_gate/weight": ((1, e), bf)})
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


def delta_rule(q, k, v, g, beta, handover, variant: str):
    """The recurrence over one sequence, a token a step: ``q``, ``k`` [S, H,
    dk], ``v`` [S, H, dv], ``g``, ``beta`` [S, H]; ``o`` [S, H, dv]."""
    s, h, dv = v.shape
    if variant == "no_decay":
        g = jnp.zeros_like(g)

    def one(state, x):
        t, q_t, k_t, v_t, g_t, b_t = x
        if variant == "state_zeroed":
            state = jnp.where(t == handover, 0.0, state)
        state = state * jnp.exp(g_t)[:, None, None]
        u = (state * k_t[:, :, None]).sum(1)                     # [H, dv]
        if variant == "no_delta":
            u = jnp.zeros_like(u)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - u))[:, None]
        return state, (state * q_t[:, :, None]).sum(1)

    state = jnp.zeros((h, q.shape[-1], dv), jnp.float32)
    _, o = jax.lax.scan(one, state, (jnp.arange(s), q, k, v, g, beta))
    return o


def linear_attention(x, p, cfg: dict, mm, variant: str, handover):
    """The gated delta-rule mixer over one sequence ``x`` [S, e]."""
    s = x.shape[0]
    hk, hv, dk, dv = linear_dims(cfg)
    key_dim, taps = hk * dk, cfg["linear_conv_kernel_dim"]
    conv_dim = 2 * key_dim + hv * dv
    qkvz = mm(x, p["mixer/in_proj_qkvz/weight"].T)
    ba = mm(x, p["mixer/in_proj_ba/weight"].T)
    mixed, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
    # the causal depthwise convolution as shifted sums: output t is
    # sum_j w[:, j] x[t - (taps - 1) + j], zeros before the sequence
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, conv_dim), mixed.dtype), mixed])
    conv = sum(padded[j:j + s] * p["mixer/conv_weight"][:, j]
               for j in range(taps))
    conv = jax.nn.silu(conv)
    rep = hv // hk
    q = jnp.repeat(conv[:, :key_dim].reshape(s, hk, dk), rep, axis=1)
    k = jnp.repeat(conv[:, key_dim:2 * key_dim].reshape(s, hk, dk), rep,
                   axis=1)
    v = conv[:, 2 * key_dim:].reshape(s, hv, dv)
    q = _l2_norm(q) * dk ** -0.5
    k = _l2_norm(k)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["mixer/A_log"]) * jax.nn.softplus(
        ba[:, hv:] + p["mixer/dt_bias"])
    o = delta_rule(q, k, v, g, beta, handover, variant)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = o * p["mixer/norm_weight"] * jax.nn.silu(z.reshape(s, hv, dv))
    return mm(o.reshape(s, hv * dv), p["mixer/out_proj/weight"].T)


def _rope(x, positions, rot: int, theta: float):
    """Rotate-half RoPE over the first ``rot`` dims of ``x`` [S, heads, d];
    the rest pass."""
    freqs = (1.0 / np.float32(theta) ** (
        np.arange(0, rot, 2, dtype=np.float32) / np.float32(rot))
    ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    turn, rest = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(turn, 2, axis=-1)
    turned = turn * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) \
        * jnp.sin(ang)
    return jnp.concatenate([turned, rest], axis=-1)


def full_attention(x, p, cfg: dict, mm, variant: str):
    """Gated GQA over one sequence ``x`` [S, e], queries in blocks, dense
    causal mask."""
    s = x.shape[0]
    d, h, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    rep, eps = h // kv, cfg["rms_norm_eps"]
    rot = int(d * cfg["partial_rotary_factor"])
    pos = jnp.arange(s, dtype=jnp.int32)
    qg = mm(x, p["attn/q_proj/weight"].T).reshape(s, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(s, h * d)
    k = mm(x, p["attn/k_proj/weight"].T).reshape(s, kv, d)
    v = mm(x, p["attn/v_proj/weight"].T).reshape(s, kv, d)
    q = _rope(_rms_norm(q, p["attn/q_norm/weight"], eps), pos, rot,
              cfg["rope_theta"])
    k = _rope(_rms_norm(k, p["attn/k_norm/weight"], eps), pos, rot,
              cfg["rope_theta"])
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
    if variant != "no_gate":
        ctx = ctx * jax.nn.sigmoid(gate)
    return mm(ctx, p["attn/o_proj/weight"].T)


def route(x, p, cfg: dict, mm, variant: str):
    """``[S, held experts]`` weights of the experts this chip holds, 0
    where not chosen: the softmax and the top k run over ALL the router's
    experts."""
    probs = jax.nn.softmax(mm(x, p["moe/router/weight"].T), axis=-1)
    w, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]
    here = (idx >= first) & (idx < first + held)
    if cfg["norm_topk_prob"]:
        if variant == "renorm_held":
            w = jnp.where(here, w, 0.0)
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
        else:
            w = w / w.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    full = jnp.zeros_like(probs).at[rows, idx].set(w)
    return full[:, first:first + held]


def expert_layer(x, p, cfg: dict, mm, variant: str):
    weights = route(x, p, cfg, mm, variant)

    def one(y, e):
        gate, up, down, w = e
        return y + w[:, None] * mm(
            jax.nn.silu(mm(x, gate)) * mm(x, up), down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["moe/experts/gate_proj"], p["moe/experts/up_proj"],
        p["moe/experts/down_proj"], weights.T))
    shared = mm(jax.nn.silu(mm(x, p["moe/shared/gate_proj/weight"].T))
                * mm(x, p["moe/shared/up_proj/weight"].T),
                p["moe/shared/down_proj/weight"].T)
    return y + jax.nn.sigmoid(mm(x, p["moe/shared_gate/weight"].T)) * shared


def layer(x, p, handover, cfg: dict, kind: str, precision: str = "float32"):
    """One block over one sequence ``x`` [S, e]; ``p`` holds the layer's
    leaves without the ``layer_n/`` prefix, in float32; ``handover`` is the
    position of the first decoded token (read by ``state_zeroed`` alone)."""
    mm = MATMULS["fp8" if precision == "fp8" else "float32"]
    eps = cfg["rms_norm_eps"]
    n = _rms_norm(x, p["input_norm/weight"], eps)
    x = x + (linear_attention(n, p, cfg, mm, precision, handover)
             if kind == LINEAR else full_attention(n, p, cfg, mm, precision))
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
         "num_key_value_heads", "partial_rotary_factor", "rope_theta",
         "linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "num_experts", "router_experts",
         "first_expert", "moe_intermediate_size",
         "shared_expert_intermediate_size", "num_experts_per_tok",
         "norm_topk_prob", "rms_norm_eps")


def _cfg_text(cfg: dict) -> str:
    import json

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
            fn = _jitted("layer", text, layer_kind(cfg, n), precision)
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
    reason: a routed model is discontinuous in its router, here with ten
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
