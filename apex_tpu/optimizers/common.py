"""Shared machinery for the fused optimizer facades.

The reference optimizers (apex/optimizers/fused_adam.py etc.) are
``torch.optim.Optimizer`` subclasses that mutate ``p.data`` in place via
multi-tensor CUDA launches. JAX state is immutable, so the facade here:

- holds the fp32 **master copy** of all parameters as ONE flat buffer
  (amp-O2-style master weights are therefore the default, as in apex when
  driven by amp), plus flat optimizer state buffers;
- ``step(grads)`` flattens the incoming grad pytree (one fused concat),
  runs the Pallas update kernel(s), and returns the updated params unflattened
  into the original dtypes/shapes;
- the whole step is jitted once with donated state buffers — zero reallocation
  per step.

Weight-decay masks (apex param_groups with wd=0 on bias/LayerNorm) are
expressed as a predicate over pytree paths mapped to a per-segment wd vector.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.ops import flat_buffer
from apex_tpu.ops.flat_buffer import LANE, FlatSpec, build_spec


def _agree_found_inf_across_model_parallel(found_inf):
    """pmax the found-inf flag over every bound model-parallel mesh axis.

    Reference: apex/transformer/amp/grad_scaler.py — GradScaler's found_inf
    is all-reduced (MAX) over the model-parallel group so TP/PP ranks agree
    on whether to skip the step. Outside shard_map this is the identity.
    """
    from jax import lax

    from apex_tpu.mesh import CONTEXT_AXIS, MODEL_AXIS, STAGE_AXIS
    from apex_tpu.transformer.tensor_parallel.mappings import axis_is_bound

    for ax in (MODEL_AXIS, STAGE_AXIS, CONTEXT_AXIS):
        if axis_is_bound(ax):
            found_inf = lax.pmax(found_inf, ax)
    return found_inf


def path_name(path) -> str:
    """'/'-joined key path for a pytree leaf (for wd-exclusion predicates)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


class FusedOptimizerBase:
    """Common state handling for FusedAdam/FusedLAMB/FusedSGD/FusedNovoGrad."""

    #: names of flat (rows, LANE) fp32 state buffers, e.g. ("m", "v")
    STATE_BUFFERS: tuple = ()

    def __init__(self, params, defaults: dict,
                 exclude_from_weight_decay: Optional[Callable[[str], bool]] = None):
        self.defaults = dict(defaults)
        self.spec: FlatSpec = build_spec(params)
        # host-side constant: staying numpy means jit embeds it as a literal
        # without a device round-trip (a device-array closure constant
        # requires a D2H copy at trace time — the bench_r03 failure mode)
        self.seg_rows = self.spec.segment_rows()
        self.master = flat_buffer.flatten(params, self.spec)
        self.state = {
            name: jnp.zeros((self.spec.total_rows, LANE), jnp.float32)
            for name in self.STATE_BUFFERS
        }
        self.step_count = jnp.zeros((), jnp.int32)

        wd = float(self.defaults.get("weight_decay", 0.0))
        if exclude_from_weight_decay is not None:
            paths, _ = jax.tree_util.tree_flatten_with_path(params)
            wd_list = [
                0.0 if exclude_from_weight_decay(path_name(p)) else wd
                for p, _ in paths
            ]
            self.wd_per_segment = jnp.asarray(wd_list, jnp.float32)
        else:
            self.wd_per_segment = None
        self._jit_step = None
        self._amp_scaler = None
        self._out_dtypes = None

    def attach_amp_scaler(self, scaler) -> None:
        """Called by amp.initialize: fuses unscale + found-inf skip + dynamic
        scale update into this optimizer's jitted step."""
        self._amp_scaler = scaler
        self._jit_step = None  # re-trace with the scaler path

    def set_output_dtypes(self, dtypes) -> None:
        """Called by amp.initialize under O2/O3: step() must return params in
        the policy-cast dtypes (master->model half copy of the reference),
        not the dtypes the optimizer was constructed with."""
        self._out_dtypes = list(dtypes)
        self._jit_step = None

    # -- torch-API parity shims ------------------------------------------------
    def zero_grad(self, set_to_none: bool = True):
        """No-op: JAX grads are values, not buffers (kept for API parity)."""

    @property
    def param_groups(self):
        """Minimal parity: one group carrying the defaults."""
        return [dict(self.defaults, params=None)]

    # -- state dict ------------------------------------------------------------
    def state_dict(self):
        return {
            "master": self.master,
            "state": dict(self.state),
            "step": self.step_count,
            "defaults": dict(self.defaults),
        }

    def load_state_dict(self, sd):
        self.master = jnp.asarray(sd["master"])
        self.state = {k: jnp.asarray(v) for k, v in sd["state"].items()}
        self.step_count = jnp.asarray(sd["step"])
        self.defaults.update(sd.get("defaults", {}))

    # -- stepping --------------------------------------------------------------
    def _update(self, g_flat, master, state, step, hyper):
        """Pure update: returns (new_master, new_state). Implemented by
        subclasses via the Pallas kernels."""
        raise NotImplementedError

    def step(self, grads, grad_scale=None, noop=None):
        """Apply one optimizer step for the given grad pytree; returns the
        updated parameter pytree (original shapes/dtypes).

        ``grad_scale`` multiplies grads inside the kernel (amp unscale + clip
        folded in); ``noop`` (0/1) skips the step (dynamic-loss-scale
        overflow), matching the reference's noop_flag semantics.
        """
        gdef = jax.tree.structure(grads)
        if gdef != self.spec.treedef:
            raise ValueError(
                f"grad pytree structure {gdef} does not match the parameter "
                f"structure this optimizer was built with ({self.spec.treedef})"
            )
        if getattr(self, "_amp_require_noop", False) and noop is None:
            # amp multi-loss dynamic mode: grads MUST come through
            # amp.unscale_and_combine (per-loss unscale + union found-inf);
            # its noop flag is the receipt — without it the grads are still
            # multiplied by the per-loss scales
            raise RuntimeError(
                "this optimizer was initialized by amp with multiple "
                "dynamically-scaled losses: combine grads with "
                "amp.unscale_and_combine and call "
                "step(grads, noop=noop)")
        if self._jit_step is None:
            spec = self.spec
            seg_rows = self.seg_rows
            scaler = self._amp_scaler
            out_dtypes = self._out_dtypes

            def _pure(g_tree, master, state, step, hyper, gs, noop_,
                      scaler_state, wd_seg):
                g_flat = flat_buffer.flatten(g_tree, spec)
                if scaler is not None:
                    # fused unscale + overflow skip (reference: scaler.py
                    # unscale + _process_optimizer's skip-on-overflow)
                    from apex_tpu.ops import optim_kernels

                    _, finite, _ = optim_kernels.global_grad_norm_and_finite(
                        g_flat, seg_rows, spec.num_tensors
                    )
                    found_inf = 1.0 - finite.astype(jnp.float32)
                    # model-parallel agreement: an inf on ONE tp/pp rank must
                    # skip the step on ALL ranks or shards diverge (reference:
                    # apex/transformer/amp/grad_scaler.py allreduces found_inf
                    # over the model-parallel group)
                    found_inf = _agree_found_inf_across_model_parallel(found_inf)
                    gs = gs / scaler_state.scale
                    noop_ = jnp.maximum(noop_, found_inf)
                    scaler_state = scaler.update(scaler_state, found_inf)
                # a skipped step must not advance the count (the reference
                # skips optimizer.step() entirely, so Adam bias correction
                # sees only applied steps)
                new_step = step + jnp.where(noop_ > 0.0, 0, 1).astype(step.dtype)
                # wd_seg rides as a traced argument (NOT a closure constant):
                # LARC temporarily nulls wd_per_segment around its inner step,
                # and a baked-in value would survive the jit cache
                new_master, new_state = self._update(
                    g_flat, master, state, new_step,
                    dict(hyper, grad_scale=gs, noop=noop_,
                         wd_per_segment=wd_seg)
                )
                params = flat_buffer.unflatten(new_master, spec, dtypes=out_dtypes)
                return params, new_master, new_state, new_step, scaler_state

            sharding = self.master.sharding
            if (len(sharding.device_set) > 1
                    and sharding.is_fully_replicated):
                # replicated state on a multi-device mesh (data
                # parallelism): Mosaic kernels cannot be partitioned
                # automatically, so every device runs the whole update
                # under shard_map
                _pure = jax.shard_map(_pure, mesh=sharding.mesh,
                                      in_specs=P(), out_specs=P(),
                                      check_vma=False)
            self._jit_step = jax.jit(_pure, donate_argnums=(1, 2))

        hyper = {k: jnp.asarray(v, jnp.float32)
                 for k, v in self.defaults.items()
                 if isinstance(v, (int, float))}
        gs = jnp.asarray(1.0 if grad_scale is None else grad_scale, jnp.float32)
        noop_ = jnp.asarray(0.0 if noop is None else noop, jnp.float32)
        sstate = self._amp_scaler.state if self._amp_scaler is not None else None
        params, self.master, self.state, self.step_count, sstate = self._jit_step(
            grads, self.master, self.state, self.step_count, hyper, gs, noop_,
            sstate, self.wd_per_segment
        )
        if self._amp_scaler is not None:
            self._amp_scaler.state = sstate
        return params
