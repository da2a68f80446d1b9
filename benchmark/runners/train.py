"""Runner of the training cells: ``make_pretrain_step`` + ``FusedLAMB.step``
as ``chip_smoke.train_loop`` drives them, one chip.

Set-up builds one object (the compiled step with its optimizer state),
drives it from the seed through its first ``FOLLOWED`` steps by the window's
own call and feed, and hands the same object to the window.  Once the window
has closed and the program's state is freed, the plain reference follows
those steps from weights of its own and the numbers are compared.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from benchmark.harness import compare, flops, runtime, traffic, weights
from benchmark.references import bert as reference

FOLLOWED = 3            # steps the reference follows
BLOCK_EVERY = 4         # steps between two reads of the clock
POOL = 16               # host batches made from the seed, fed in turn


def program_config(cfg: dict):
    """The program's ``BertConfig`` for a configuration file."""
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


def hyper(mix: dict) -> dict:
    return {"lr": mix["lr"], "beta1": mix["betas"][0],
            "beta2": mix["betas"][1], "eps": mix["eps"],
            "weight_decay": mix["weight_decay"],
            "max_grad_norm": mix["max_grad_norm"]}


class Program:
    """The timed path: the grad step and the optimizer with its state."""

    def __init__(self, cfg: dict, mix: dict, seed: int, grad_step=None):
        import jax

        from apex_tpu.models import BertForPreTraining, make_pretrain_step
        from apex_tpu.optimizers import FusedLAMB

        self.model = BertForPreTraining(program_config(cfg))
        z = jax.ShapeDtypeStruct((1, 8), np.int32)
        like = jax.eval_shape(self.model.init, jax.random.PRNGKey(0), z, z,
                              z)["params"]
        self.like = like
        self.seed = seed
        self.params = weights.make_like(like, seed)
        hp = hyper(mix)
        self.opt = FusedLAMB(
            self.params, lr=hp["lr"], betas=(hp["beta1"], hp["beta2"]),
            eps=hp["eps"], weight_decay=hp["weight_decay"],
            max_grad_norm=hp["max_grad_norm"],
            exclude_from_weight_decay=lambda n: not reference.decayed(n))
        # a proof script that reads many seeds in one process hands the
        # jitted step on; a run builds it here
        self.grad_step = grad_step or make_pretrain_step(self.model)
        self.steps = 0

    def step(self, host_batch: Dict[str, np.ndarray]):
        """One training step as the window makes it; returns the loss, not
        yet waited for."""
        import jax
        import jax.numpy as jnp

        with runtime.annotate("device_put"):
            batch = jax.device_put(host_batch)
        with runtime.annotate("grad_step"):
            loss, grads = self.grad_step(self.params, batch,
                                         jnp.int32(self.steps))
        with runtime.annotate("opt.step"):
            self.params = self.opt.step(grads)
        self.steps += 1
        return loss

    # -- readings for the comparison ----------------------------------------

    def first_gradient_norms(self, beta1: float) -> Dict[str, float]:
        """Per-leaf norm of the first gradient as the optimizer got it,
        from its first moment after one step: m1 = (1 - beta1) * g."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.ops import flat_buffer

        spec = self.opt.spec
        norms = jax.jit(lambda m: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(x * x)) / (1.0 - beta1),
            flat_buffer.unflatten(m, spec)))(self.opt.state["m"])
        return {k: float(v) for k, v in weights.table_named(norms).items()}

    def change_norms(self) -> Dict[str, float]:
        """Per-leaf norm of (parameters now - parameters at the start); the
        start is made again from the seed, for the length of this call."""
        import jax
        import jax.numpy as jnp

        start = weights.make_like(self.like, self.seed)
        norms = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))(
            self.params, start)
        return {k: float(v) for k, v in weights.table_named(norms).items()}


def first_steps(prog: Program, batches: List[dict], beta1: float) -> dict:
    """Drives ``prog`` through the followed steps by the window's own call
    and feed; returns what the comparison reads of them."""
    seen = {"losses": []}
    for n in range(FOLLOWED):
        seen["losses"].append(float(prog.step(batches[n])))
        if n == 0:
            seen["grad_norms"] = prog.first_gradient_norms(beta1)
    seen["change_norms"] = prog.change_norms()
    return seen


def follow_reference(cfg: dict, mix: dict, seed: int, batches: List[dict],
                     precision: str = "float32") -> dict:
    import jax.numpy as jnp

    params = weights.make_weights(reference.param_table(cfg), seed)
    return reference.train(
        params, [{k: jnp.asarray(v) for k, v in b.items()} for b in batches],
        cfg, hyper(mix), precision)


def run(ctx) -> dict:
    """One run of a training cell; ``ctx`` is ``run.Context``."""
    import jax

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    tokens_per_step = mix["batch"] * mix["seq_len"]
    batches = traffic.train_batches(mix, cfg["held_vocab"],
                                    cfg["type_vocab_size"], seed, POOL)
    prog = Program(cfg, mix, seed)
    seen = first_steps(prog, batches, mix["betas"][0])
    jax.block_until_ready(prog.params)

    compiles0 = ctx.compiles.count
    traced = None
    ctx.window_opens()
    t0 = time.perf_counter()
    steps = 0
    trace_at = ctx.seconds / 3.0 if ctx.trace else None
    traced_steps = mix["traced_steps"]
    loss = None
    while True:
        if trace_at is not None and time.perf_counter() - t0 >= trace_at:
            trace_at = None
            jax.block_until_ready(loss)
            with runtime.TracedWindow(runtime.trace_dir()) as traced:
                for _ in range(traced_steps):
                    loss = prog.step(batches[prog.steps % POOL])
                    steps += 1
                jax.block_until_ready((loss, prog.params))
        for _ in range(BLOCK_EVERY):
            loss = prog.step(batches[prog.steps % POOL])
            steps += 1
        jax.block_until_ready(loss)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    jax.block_until_ready(prog.params)
    elapsed = time.perf_counter() - t0
    last_loss = float(loss)
    compiles = ctx.compiles.count - compiles0
    peak_bytes = runtime.memory_peak_bytes(ctx.devices)

    # the program's state leaves the device before the reference runs
    del prog, loss
    gc.collect()
    t_ref = time.perf_counter()
    ref = follow_reference(cfg, mix, seed, batches[:FOLLOWED])
    numbers = compare.train_numbers(seen, ref)
    reference_s = time.perf_counter() - t_ref
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last_loss) else 1.0

    flops_per_token = flops.bert_train_flops_per_token(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], vocab=cfg["held_vocab"],
        seq_len=mix["seq_len"], mlm_k=mix["mlm_per_seq"])
    reading = {
        "flops_per_token": flops_per_token,
        "tokens_per_step": tokens_per_step,
        "shapes": {"batch": mix["batch"], "seq_len": mix["seq_len"],
                   "heads": cfg["num_attention_heads"],
                   "head_dim": cfg["hidden_size"]
                   // cfg["num_attention_heads"],
                   "layers": cfg["num_hidden_layers"]},
    }
    if traced is not None:
        reading.update(trace=traced.trace, window_s=traced.window_s,
                       steps=traced_steps,
                       tokens=traced_steps * tokens_per_step)
    return {
        "metrics": {"train_tokens_per_s": steps * tokens_per_step / elapsed},
        "attempted": steps, "failed": 0, "numbers": numbers,
        "memory_peak_bytes": peak_bytes, "reading": reading,
        "notes": {"steps": steps, "window_compiles": compiles, "elapsed_s": elapsed,
                  "reference_s": reference_s, "last_loss": last_loss,
                  "first_losses": seen["losses"]},
    }
