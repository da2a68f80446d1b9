"""Flagship benchmark: BERT-Large pretraining step (BASELINE.md config #2)
plus the fused-optimizer step-time microbench (BASELINE metric #2).

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "platform": ...,
   "device_kind": ..., "n_chips": N, ...}
vs_baseline = measured MFU / 0.45 (the BASELINE.json north-star MFU target).
Extra keys: "mfu", "step_ms", "optimizer_speedup" (fused flat-buffer LAMB
step vs naive per-param jitted optax-style update). Any failure is a
traceback and a non-zero exit; a device whose peak is not in ``PEAK_FLOPS``
(a CPU included) is a failure. Diagnostics go to stderr.

The train step runs data-parallel over ALL local devices: a ``shard_map``
over a ``data`` mesh (batch split, params and optimizer state replicated,
grads averaged by ``DistributedDataParallel.allreduce_gradients``) — plain
``jit`` cannot partition the Mosaic kernels. On one chip this is the
identity.
"""

import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# bf16 peak FLOPs/s per chip by device kind (public TPU specs)
PEAK_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for token, f in PEAK_FLOPS:
        if token in kind:
            return f
    raise ValueError(
        f"no peak FLOP/s known for device kind {device.device_kind!r} "
        f"(platform {device.platform}); add it to PEAK_FLOPS with its source")


def model_flops_per_token(cfg, seq_len: int, mlm_k: int = None) -> float:
    """Matmul FLOPs per token, fwd+bwd (bwd = 2x fwd), BERT-Large shape.

    ``mlm_k``: with the gathered MLM head (max_predictions_per_seq), the
    dense+decode GEMMs run at K of S positions — count only that fraction
    so MFU stays honest about the work actually done."""
    e, i, L, v = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    per_layer = 8 * e * e + 4 * seq_len * e + 4 * e * i
    head_frac = 1.0 if mlm_k is None else mlm_k / seq_len
    head = (2 * e * e + 2 * e * v) * head_frac
    return 3.0 * (L * per_layer + head)


def bench_optimizer_speedup(params_like, steps: int = 20) -> float:
    """BASELINE metric #2: fused flat-buffer LAMB step time vs a naive
    per-param jitted update (optax-style tree of adam+trust-ratio ops)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedLAMB

    params = params_like
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 1e-3, params)

    fused = FusedLAMB(params, lr=1e-4, weight_decay=0.01)
    fused.step(grads)  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fused.step(grads)
    jax.block_until_ready(out)
    fused_dt = (time.perf_counter() - t0) / steps

    # naive: per-param adam + per-tensor trust ratio, jitted as one fn
    def naive_update(params, grads, m, v, count):
        b1, b2, eps, lr, wd = 0.9, 0.999, 1e-6, 1e-4, 0.01
        gsq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                  for g in jax.tree.leaves(grads))
        gnorm = jnp.sqrt(gsq)
        clip = jnp.where(gnorm > 1.0, 1.0 / gnorm, 1.0)
        count = count + 1
        rbc1 = 1.0 / (1.0 - b1 ** count)
        rbc2 = 1.0 / (1.0 - b2 ** count)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * clip
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m * rbc1) / (jnp.sqrt(v * rbc2) + eps) + wd * p
            pn = jnp.sqrt(jnp.sum(p.astype(jnp.float32) ** 2))
            un = jnp.sqrt(jnp.sum(u ** 2))
            ratio = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
            return p - lr * ratio * u, m, v

        out = jax.tree.map(upd, params, grads, m, v)
        # leaves are 3-tuples: select tuple elements, not array rows
        is_tup = lambda x: isinstance(x, tuple)  # noqa: E731
        new_p = jax.tree.map(lambda t: t[0], out, is_leaf=is_tup)
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=is_tup)
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=is_tup)
        return new_p, new_m, new_v, count

    naive = jax.jit(naive_update, donate_argnums=(0, 2, 3))
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    count = jnp.zeros((), jnp.int32)
    p = params
    p, m, v, count = naive(p, grads, m, v, count)  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        p, m, v, count = naive(p, grads, m, v, count)
    jax.block_until_ready(p)
    naive_dt = (time.perf_counter() - t0) / steps
    log(f"optimizer step: fused {fused_dt*1e3:.2f}ms  "
        f"naive {naive_dt*1e3:.2f}ms  speedup {naive_dt/fused_dt:.2f}x")
    return naive_dt / fused_dt


def run_workload(devs, batch_per_chip: int, seq_len: int, steps: int,
                 remat: bool):
    """Build + shard + compile + time one measurement."""
    import dataclasses

    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu.models import (BertForPreTraining, bert_large_config,
                                 make_pretrain_step, synthetic_batch)
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.parallel import DistributedDataParallel

    peak = peak_flops(devs[0])          # an unknown device fails here
    n_chips = len(devs)
    batch_size = batch_per_chip * n_chips

    cfg = bert_large_config(max_position_embeddings=max(512, seq_len))
    if remat:
        # trades backward FLOPs for activation memory — required for the
        # larger per-chip batches
        cfg = dataclasses.replace(cfg, remat=True)
        log("remat enabled")
    model = BertForPreTraining(cfg)
    rng = np.random.default_rng(0)
    batch = synthetic_batch(rng, cfg, batch_size, seq_len)

    mesh = Mesh(np.asarray(devs), ("data",))
    repl = NamedSharding(mesh, P())
    batch = jax.device_put(batch, NamedSharding(mesh, P("data")))

    log("initializing BERT params...")
    params = jax.jit(lambda key: model.init(
        key, batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"])["params"])(jax.random.PRNGKey(0))
    params = jax.device_put(params, repl)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"params: {n_params/1e6:.1f}M  batch={batch_size} ({batch_per_chip}/chip"
        f" x {n_chips} chips)  seq={seq_len}")

    grad_step = make_pretrain_step(model)
    ddp = DistributedDataParallel(model)

    def dp_step(p, b, i):
        loss, grads = grad_step(p, b, i)
        return lax.pmean(loss, "data"), ddp.allreduce_gradients(grads)

    step = jax.jit(jax.shard_map(
        dp_step, mesh=mesh, in_specs=(P(), P("data"), P()), out_specs=P(),
        check_vma=False))
    opt = FusedLAMB(
        params, lr=1e-4, weight_decay=0.01,
        exclude_from_weight_decay=lambda n: "bias" in n or "norm" in n.lower())

    def train_step(p, i):
        loss, grads = step(p, batch, i)
        return loss, opt.step(grads)

    log("compiling + warmup...")
    t0 = time.perf_counter()
    loss, params = train_step(params, 0)
    jax.block_until_ready(params)
    log(f"first step (compile) {time.perf_counter()-t0:.1f}s loss={float(loss):.3f}")
    loss, params = train_step(params, 1)
    jax.block_until_ready(params)

    # the record carries how many devices really held a batch shard
    x = batch["input_ids"]
    n_shards = len({s.device.id for s in x.addressable_shards})

    log(f"timing {steps} steps...")
    t0 = time.perf_counter()
    for i in range(steps):
        loss, params = train_step(params, 2 + i)
    jax.block_until_ready(params)
    dt = (time.perf_counter() - t0) / steps

    tokens = batch_size * seq_len
    tok_per_sec_chip = tokens / dt / n_chips
    mlm_k = batch["mlm_positions"].shape[1]
    flops = model_flops_per_token(cfg, seq_len, mlm_k) * tokens
    mfu = flops / dt / (peak * n_chips)
    log(f"step {dt*1e3:.1f}ms  loss={float(loss):.3f}  "
        f"tokens/s/chip={tok_per_sec_chip:.0f}  MFU={mfu*100:.1f}%")
    return dict(tok_per_sec_chip=tok_per_sec_chip, mfu=mfu, dt=dt,
                params=params, n_shards=n_shards)


def main():
    import jax

    from apex_tpu.utils.compile_cache import enable_compile_cache

    log(f"compilation cache: {enable_compile_cache()}")
    devs = jax.devices()
    log(f"backend: {len(devs)} x {devs[0].device_kind} ({devs[0].platform})")

    batch_per_chip = int(os.environ.get("APEX_TPU_BENCH_BATCH", "8"))
    seq_len = int(os.environ.get("APEX_TPU_BENCH_SEQ", "512"))
    steps = int(os.environ.get("APEX_TPU_BENCH_STEPS", "10"))
    remat = os.environ.get("APEX_TPU_BENCH_REMAT") == "1"

    result = run_workload(devs, batch_per_chip, seq_len, steps, remat)
    opt_speedup = bench_optimizer_speedup(result["params"])
    print(json.dumps({
        "metric": "bert_large_pretrain_tokens_per_sec_per_chip",
        "value": round(result["tok_per_sec_chip"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(result["mfu"] / 0.45, 4),
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "n_chips": len(devs), "n_data_shards": result["n_shards"],
        "mfu": round(result["mfu"], 4),
        "step_ms": round(result["dt"] * 1e3, 2),
        "optimizer_speedup": round(opt_speedup, 3)}), flush=True)


if __name__ == "__main__":
    main()
