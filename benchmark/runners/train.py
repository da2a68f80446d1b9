"""Runner of the training cells: the family's grad step + ``FusedLAMB.step``
as ``chip_smoke.train_loop`` drives them, on the cell's chips.

The model is the configuration's family (``benchmark/families/``); the mesh
is the cell's ``chips``.  On one chip the step is the family's jitted grad
step and the optimizer's.  On several it is the data-parallel step of
``chip_smoke.train_loop(mesh=...)``: a ``shard_map`` over ``data`` of the
grad step, the loss averaged and the gradients exchanged by
``DistributedDataParallel.allreduce_gradients``, parameters and optimizer
state replicated, each host batch ``device_put`` split over ``data`` (plain
``jit`` over a sharded batch will not do: Mosaic kernels are not partitioned
automatically).

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

from benchmark import families
from benchmark.harness import compare, runtime, traffic, weights

FOLLOWED = 3            # steps the reference follows
BLOCK_EVERY = 4         # steps between two reads of the clock
POOL = 16               # host batches made from the seed, fed in turn


def data_parallel(grad_step, model, mesh):
    """The grad step over ``mesh``: every chip takes its rows of the batch,
    the loss is averaged and the gradients exchanged.  The function keeps
    the one-chip step's name, so that the trace's ``XLA Modules`` line reads
    ``jit_loss_fn`` on any number of chips (``grad_step_ms.train``)."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.mesh import DATA_AXIS
    from apex_tpu.parallel import DistributedDataParallel

    ddp = DistributedDataParallel(model)

    def loss_fn(params, batch, step):
        loss, grads = grad_step(params, batch, step)
        return lax.pmean(loss, DATA_AXIS), ddp.allreduce_gradients(grads)

    return jax.jit(jax.shard_map(
        loss_fn, mesh=mesh, in_specs=(P(), P(DATA_AXIS), P()),
        out_specs=P(), check_vma=False))


class Program:
    """The timed path: the grad step and the optimizer with its state."""

    def __init__(self, cfg: dict, mix: dict, seed: int, devices,
                 grad_step=None):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from apex_tpu.mesh import DATA_AXIS
        from apex_tpu.optimizers import FusedLAMB

        family = families.load(cfg)
        self.model = family.model(cfg)
        self.like = family.param_shapes(self.model)
        self.seed = seed
        self.params = weights.make_like(self.like, seed)
        self.mesh, self.feed, self._drift = None, None, None
        if len(devices) > 1:
            self.mesh = Mesh(np.array(devices), (DATA_AXIS,))
            self.feed = NamedSharding(self.mesh, P(DATA_AXIS))
            self.params = jax.device_put(self.params,
                                         NamedSharding(self.mesh, P()))
        hp = traffic.train_hyper(mix)
        self.opt = FusedLAMB(
            self.params, lr=hp["lr"], betas=(hp["beta1"], hp["beta2"]),
            eps=hp["eps"], weight_decay=hp["weight_decay"],
            max_grad_norm=hp["max_grad_norm"],
            exclude_from_weight_decay=lambda n: not family.decayed(n))
        # a proof script that reads many seeds in one process hands the
        # jitted step on; a run builds it here
        if grad_step is None:
            grad_step = family.grad_step(self.model)
            if self.mesh is not None:
                grad_step = data_parallel(grad_step, self.model, self.mesh)
        self.grad_step = grad_step
        self.steps = 0

    def step(self, host_batch: Dict[str, np.ndarray]):
        """One training step as the window makes it; returns the loss, not
        yet waited for."""
        import jax
        import jax.numpy as jnp

        with runtime.annotate("device_put"):
            batch = jax.device_put(host_batch, self.feed)
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


    def replica_drift(self) -> float:
        """The largest norm, over leaves and chips, of (the leaf on a chip -
        the leaf on chip 0) against chip 0's norm of it.  The update is
        deterministic, so with the exchange every chip steps alike and this
        is 0; parameters claimed replicated are each chip's own buffer."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from apex_tpu.mesh import DATA_AXIS

        def drift(params):
            first = lax.axis_index(DATA_AXIS) == 0

            def leaf(x):
                x = x.astype(jnp.float32)
                x0 = lax.psum(jnp.where(first, x, 0.0), DATA_AXIS)
                gap = lax.pmax(jnp.sqrt(jnp.sum(jnp.square(x - x0))),
                               DATA_AXIS)
                return gap / jnp.maximum(jnp.sqrt(jnp.sum(x0 * x0)), 1e-30)

            # a NaN is the largest: jnp.max hands it on and the verdict
            # fails a number that is not finite
            return jnp.max(jnp.stack(jax.tree.leaves(
                jax.tree.map(leaf, params))))

        if self._drift is None:     # read twice in a run, built once
            self._drift = jax.jit(jax.shard_map(
                drift, mesh=self.mesh, in_specs=P(), out_specs=P(),
                check_vma=False))
        return float(self._drift(self.params))


def first_steps(prog: Program, batches: List[dict], beta1: float) -> dict:
    """Drives ``prog`` through the followed steps by the window's own call
    and feed; returns what the comparison reads of them."""
    seen = {"losses": []}
    for n in range(FOLLOWED):
        seen["losses"].append(float(prog.step(batches[n])))
        if n == 0:
            seen["grad_norms"] = prog.first_gradient_norms(beta1)
    seen["change_norms"] = prog.change_norms()
    if prog.mesh is not None:
        seen["replica_drift"] = prog.replica_drift()
    return seen


def numbers_of(seen: dict, ref: dict) -> Dict[str, float]:
    """The numbers of the followed steps: the comparison with the reference,
    and across chips the drift between the replicas."""
    numbers = compare.train_numbers(seen, ref)
    if "replica_drift" in seen:
        numbers["replica_drift"] = seen["replica_drift"]
    return numbers


def run(ctx) -> dict:
    """One run of a training cell; ``ctx`` is ``run.Context``."""
    import jax

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    family = families.load(cfg)
    chips = len(ctx.devices)
    phases = runtime.Phases()
    if mix["batch"] % chips:
        raise ValueError(f"batch {mix['batch']} does not split over {chips} "
                         f"chips")
    tokens_per_step = mix["batch"] * mix["seq_len"]
    batches = family.batches(cfg, mix, seed, POOL)
    prog = Program(cfg, mix, seed, ctx.devices)
    jax.block_until_ready(prog.opt.master)
    phases.mark("program_s")
    seen = first_steps(prog, batches, mix["betas"][0])
    jax.block_until_ready(prog.params)
    phases.mark("first_steps_s")

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
    if prog.mesh is not None:
        # the replicas after every step of the window too
        seen["replica_drift"] = max(seen["replica_drift"],
                                    prog.replica_drift())

    phases.mark("window_s")

    # the program's state leaves the device before the reference runs
    del prog, loss
    gc.collect()
    ref = family.follow(cfg, mix, seed, batches[:FOLLOWED])
    numbers = numbers_of(seen, ref)
    phases.mark("reference_s")
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last_loss) else 1.0

    reading = {
        "flops_per_token": family.train_flops_per_token(cfg, mix),
        "tokens_per_step": tokens_per_step,
        "shapes": family.shapes(cfg, mix, chips),
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
                  "reference_s": phases["reference_s"],
                  "phases": dict(phases),
                  "last_loss": last_loss,
                  "first_losses": seen["losses"]},
    }
