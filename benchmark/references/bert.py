"""Plain reference of BERT pretraining + LAMB: float32 ``jax.numpy``, no
kernels, nothing imported from the program, weights made from the seed.

Follows Devlin et al. 2018 (post-LN encoder, tied MLM decoder, NSP over
[CLS]) and You et al. 2019 (LAMB) as NVIDIA's apex states them
(``multi_tensor_lamb.cu``: global-norm clip folded into the gradient,
``grad_averaging``, bias correction, the trust ratio only where weight decay
applies).  Departures from the published model, shared with the program and
named in the configuration's ``assumed``: tanh-approximated GELU, vocabulary
padded to 30528, the MLM head evaluated at the masked positions only.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.references.precision import MATMULS


def param_table(cfg: dict) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of every parameter, in the names the
    program's tree flattens to."""
    e, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["held_vocab"]
    f32 = jnp.float32
    t = {
        "word_embeddings": ((v, e), f32),
        "position_embeddings": ((cfg["max_position_embeddings"], e), f32),
        "token_type_embeddings": ((cfg["type_vocab_size"], e), f32),
        "mlm_dense_weight": ((e, e), f32), "mlm_dense_bias": ((e,), f32),
        "mlm_output_bias": ((v,), f32),
        "pooler_weight": ((e, e), f32), "pooler_bias": ((e,), f32),
        "nsp_weight": ((e, 2), f32), "nsp_bias": ((2,), f32),
    }
    for norm in ("embedding_norm", "mlm_norm"):
        t[f"{norm}/weight"] = ((e,), f32)
        t[f"{norm}/bias"] = ((e,), f32)
    for n in range(cfg["num_hidden_layers"]):
        p = f"layer_{n}"
        t.update({
            f"{p}/attention/qkv_weight": ((e, 3 * e), f32),
            f"{p}/attention/qkv_bias": ((3 * e,), f32),
            f"{p}/attention/out_weight": ((e, e), f32),
            f"{p}/attention/out_bias": ((e,), f32),
            f"{p}/attention_norm/weight": ((e,), f32),
            f"{p}/attention_norm/bias": ((e,), f32),
            f"{p}/mlp_weight1": ((e, i), f32), f"{p}/mlp_bias1": ((i,), f32),
            f"{p}/mlp_weight2": ((i, e), f32), f"{p}/mlp_bias2": ((e,), f32),
            f"{p}/mlp_norm/weight": ((e,), f32),
            f"{p}/mlp_norm/bias": ((e,), f32),
        })
    return t


def decayed(name: str) -> bool:
    """The recipe's parameter groups: no weight decay on biases and norms."""
    return not ("bias" in name or "norm" in name.lower())


def _layer_norm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


def loss_fn(params, batch, cfg: dict, precision: str = "float32"):
    """MLM + NSP loss of one batch; mean MLM loss over predicted positions
    (label 0 = none), mean NSP loss over rows."""
    mm = MATMULS[precision]
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d = e // h
    eps = cfg["layer_norm_eps"]
    ids = batch["input_ids"]
    b, s = ids.shape
    x = (params["word_embeddings"][ids]
         + params["position_embeddings"][None, :s]
         + params["token_type_embeddings"][batch["token_type_ids"]])
    x = _layer_norm(x, params["embedding_norm/weight"],
                    params["embedding_norm/bias"], eps)
    visible = batch["attention_mask"].astype(bool)[:, None, None, :]

    def layer(x, p):
        qkv = mm(x, p["attention/qkv_weight"]) + p["attention/qkv_bias"]
        q, k, v = (t.reshape(b, s, h, d).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, e)
        attn = mm(ctx, p["attention/out_weight"]) + p["attention/out_bias"]
        x = _layer_norm(x + attn, p["attention_norm/weight"],
                        p["attention_norm/bias"], eps)
        mid = _gelu(mm(x, p["mlp_weight1"]) + p["mlp_bias1"])
        out = mm(mid, p["mlp_weight2"]) + p["mlp_bias2"]
        return _layer_norm(x + out, p["mlp_norm/weight"],
                           p["mlp_norm/bias"], eps)

    # recomputing a layer in the backward pass keeps float32 activations of
    # 24 layers inside the chip's memory; it changes no number
    layer = jax.checkpoint(layer)
    for n in range(cfg["num_hidden_layers"]):
        prefix = f"layer_{n}/"
        x = layer(x, {k[len(prefix):]: v for k, v in params.items()
                      if k.startswith(prefix)})

    picked = jnp.take_along_axis(
        x, batch["mlm_positions"][..., None], axis=1)
    hm = _gelu(mm(picked, params["mlm_dense_weight"])
               + params["mlm_dense_bias"])
    hm = _layer_norm(hm, params["mlm_norm/weight"], params["mlm_norm/bias"],
                     eps)
    logits = mm(hm, params["word_embeddings"].T) + params["mlm_output_bias"]
    labels = batch["mlm_gathered_labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    tok = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    predicted = labels != 0
    mlm = jnp.where(predicted, tok, 0.0).sum() / jnp.maximum(
        predicted.sum(), 1)

    pooled = jnp.tanh(mm(x[:, 0], params["pooler_weight"])
                      + params["pooler_bias"])
    nsp_logits = mm(pooled, params["nsp_weight"]) + params["nsp_bias"]
    nsp = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits, axis=-1),
                               batch["nsp_labels"][:, None], axis=-1).mean()
    return mlm + nsp


def lamb_step(params, grads, m, v, step: int, hp: dict):
    """One LAMB step over dicts of leaves; returns (params, m, v, g_used)
    where ``g_used`` is the clipped gradient the moments were fed."""
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    max_norm = hp["max_grad_norm"]
    clip = jnp.where((max_norm > 0) & (gnorm > max_norm), max_norm / gnorm,
                     1.0)
    rbc1 = 1.0 / (1.0 - b1 ** step)
    rbc2 = 1.0 / (1.0 - b2 ** step)
    new_p, new_m, new_v, used = {}, {}, {}, {}
    for name, p in params.items():
        g = grads[name] * clip
        wd = hp["weight_decay"] if decayed(name) else 0.0
        mi = b1 * m[name] + (1.0 - b1) * g
        vi = b2 * v[name] + (1.0 - b2) * g * g
        u = (mi * rbc1) / (jnp.sqrt(vi * rbc2) + eps) + wd * p
        pn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        ratio = jnp.where((pn > 0) & (un > 0), pn / jnp.maximum(un, 1e-30),
                          1.0)
        if wd == 0.0:
            ratio = 1.0
        new_p[name] = p - hp["lr"] * ratio * u
        new_m[name], new_v[name], used[name] = mi, vi, g
    return new_p, new_m, new_v, used


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in tree.items()}


_KEYS = ("hidden_size", "num_attention_heads", "num_hidden_layers",
         "layer_norm_eps")


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, hp_items: tuple, precision: str):
    """The jitted loss-and-gradient and update of one configuration, built
    once in a process."""
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, cfg=dict(cfg_items), precision=precision)))
    update = jax.jit(functools.partial(lamb_step, hp=dict(hp_items)),
                     static_argnames=("step",))
    return grad, update


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)


def _blockwise(grad, params, batch, block_rows: int):
    """Loss and gradient of ``batch`` as the mean over its blocks of
    ``block_rows`` rows, one block on the device at a time.  Equal to the
    whole batch's where every row predicts as many positions (the MLM loss
    is a mean over predicted positions, the NSP loss over rows)."""
    rows = batch["input_ids"].shape[0]
    if rows % block_rows:
        raise ValueError(f"{rows} rows do not split into blocks of "
                         f"{block_rows}")
    total = None
    for start in range(0, rows, block_rows):
        part = grad(params, {k: v[start:start + block_rows]
                             for k, v in batch.items()})
        total = part if total is None else _add(total, part)
    return jax.tree.map(lambda x: x * (block_rows / rows), total)


def train(params, batches, cfg: dict, hp: dict, precision: str = "float32",
          block_rows: int = None):
    """Follow ``len(batches)`` steps from ``params``.  Returns the losses,
    the per-leaf norms of the first (clipped) gradient and of the
    parameters' change after the last step.  With ``block_rows`` a batch's
    gradient is accumulated over blocks of that many rows."""
    grad, update = _programs(tuple((k, cfg[k]) for k in _KEYS),
                             tuple(sorted(hp.items())), precision)
    start = params
    m = {k: jnp.zeros_like(x) for k, x in params.items()}
    v = {k: jnp.zeros_like(x) for k, x in params.items()}
    losses, first = [], None
    for n, batch in enumerate(batches, start=1):
        if block_rows and block_rows < batch["input_ids"].shape[0]:
            loss, grads = _blockwise(grad, params, batch, block_rows)
        else:
            loss, grads = grad(params, batch)
        params, m, v, used = update(params, grads, m, v, step=n)
        losses.append(float(loss))
        if first is None:
            first = {k: float(x) for k, x in
                     jax.jit(leaf_norms)(used).items()}
        del grads, used
    moved = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(params, start)
    return {"losses": losses, "grad_norms": first,
            "change_norms": {k: float(x) for k, x in moved.items()}}
