#!/usr/bin/env python3
"""First-light check on the chip: serve GPT-2-small, train BERT-Large.

One process, the library's normal entry points, random weights from
``--seed``.  It refuses to start without a TPU and never falls back to the
CPU; any failed check raises, so the exit code is non-zero and the closing
result line is never printed.

    python chip_smoke.py                  # one chip: serve, then train
    python chip_smoke.py --phase serve    # one of the two
    python chip_smoke.py --multichip      # four chips: TP serving, DP training

Phases
  serve   ``PagedDecodeEngine`` behind ``ServingFrontend`` with the
          background pump and the HTTP front door, 11 mixed-length requests
          over 4 slots with a shared 64-token header, twice; every stream is
          compared with lock-step ``generate``.
  train   ``make_pretrain_step`` + ``FusedLAMB.step`` on BERT-Large,
          batch 8 x seq 512, 5 steps on one repeated synthetic batch.
  --multichip
          the same requests through ``TensorParallelPagedEngine`` at tp=4
          against the one-chip engine, and the BERT-Large step data-parallel
          over the four chips against the same global batch on one chip.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Timings printed on earlier lines are smoke timings, not benchmark metrics.

The phase functions take a model config and sizes, so
``tests/test_chip_smoke.py`` rehearses them at the tiny configs on the CPU;
``main()`` has no CPU mode and no size switch.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from typing import NamedTuple, Sequence

ONE_CHIP_PHASES = ("serve", "train")

#: generated token i may differ from the reference's argmax only where the
#: reference itself cannot tell the two apart in bf16: its logit for the
#: emitted token lies within 4 bf16 ulps (2**-6 of the largest logit
#: magnitude at that position) of its maximum
NEAR_TIE_REL = 2.0 ** -6

#: bf16 activations reduce in a different order on a sharded batch; the
#: losses of the data-parallel and the one-chip run must agree to this
#: (the first four-chip run showed 1.5e-4)
DP_LOSS_RTOL = 2e-3


class Spec(NamedTuple):
    """One request of the smoke traffic."""

    prompt_len: int
    new_tokens: int
    #: the prompt starts with the shared header
    header: bool = False
    #: wave 1 is submitted once wave 0's header request has retired (the
    #: radix cache inserts at retirement, so only a later wave can hit)
    wave: int = 0
    #: submitted through POST /v1/generate
    http: bool = False


#: GPT-2-small traffic: prompts 32-512, 16-64 new tokens, 11 requests over
#: 4 slots.  Five distinct (prompt, new) shapes keep the lock-step
#: reference at five compiles.
GPT2S_TRAFFIC = (
    Spec(128, 32, header=True),
    Spec(32, 16),
    Spec(250, 64),
    Spec(512, 48),
    Spec(32, 16),
    Spec(250, 64),
    Spec(512, 48),
    Spec(200, 24),
    Spec(128, 32, header=True, wave=1),
    Spec(200, 24, header=True, wave=1),
    Spec(128, 32, header=True, wave=1, http=True),
)


@dataclasses.dataclass(frozen=True)
class ServeSizes:
    traffic: Sequence[Spec] = GPT2S_TRAFFIC
    header_len: int = 64
    num_slots: int = 4
    page_size: int = 16
    sync_every: int = 4
    pool_bytes: int = 2 * 2 ** 30       # a deployment's pool, not a test's
    stream_timeout_s: float = 600.0


@dataclasses.dataclass(frozen=True)
class TrainSizes:
    batch_size: int = 8
    seq_len: int = 512
    steps: int = 5
    lr: float = 1e-3


def log(*parts) -> None:
    print(*parts, flush=True)


# --------------------------------------------------------------------------
# arguments
# --------------------------------------------------------------------------

def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="Serve GPT-2-small and train BERT-Large on the TPU.")
    ap.add_argument("--phase", choices=ONE_CHIP_PHASES, action="append",
                    help="run only this one-chip phase (repeatable; "
                         "default: serve, then train)")
    ap.add_argument("--multichip", action="store_true",
                    help="needs 4 chips: tp=4 serving against the one-chip "
                         "engine and dp=4 training against one chip, and "
                         "no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the prompts and the batch")
    args = ap.parse_args(argv)
    if args.multichip and args.phase:
        ap.error("--multichip runs its own two phases; drop --phase")
    return args


def selected_phases(args) -> tuple:
    if args.multichip:
        return ("tp_serve", "dp_train")
    if not args.phase:
        return ONE_CHIP_PHASES
    return tuple(p for p in ONE_CHIP_PHASES if p in args.phase)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def build_requests(seed: int, vocab_size: int, sizes: ServeSizes):
    """Prompts for one pass over ``sizes.traffic``: a fresh header and
    fresh tails from ``seed``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    header = rng.integers(0, vocab_size, sizes.header_len)
    prompts = []
    for spec in sizes.traffic:
        tail_len = spec.prompt_len - (sizes.header_len if spec.header else 0)
        tail = rng.integers(0, vocab_size, tail_len)
        prompts.append(np.concatenate([header, tail] if spec.header
                                      else [tail]).astype(np.int32))
    return prompts


def pool_pages(cfg, sizes: ServeSizes) -> int:
    """Pages (plus the null page) that ``sizes.pool_bytes`` of K/V hold for
    the unsharded model ``cfg``."""
    from apex_tpu.serving import kv_pool

    return 1 + sizes.pool_bytes // kv_pool.page_bytes(cfg, sizes.page_size)


def compile_seconds() -> float:
    """Seconds this process has spent in XLA backend compiles so far, as
    the compile watcher's ``jit.compile_ms`` histograms saw them."""
    from apex_tpu.utils import metrics

    return sum(h["sum"] for h in metrics.snapshot()["histograms"]
               if h["name"] == "jit.compile_ms") / 1e3


def custom_call_sites(jitted, *args) -> int:
    """``tpu_custom_call`` sites in the program ``jitted`` compiles to for
    ``args`` — 0 means its Pallas kernels did not reach Mosaic."""
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


def decode_chunk_kernel_sites(frontend) -> int:
    import jax
    import jax.numpy as jnp

    eng = frontend.engine
    n = eng.num_slots

    def slot(dtype, *tail):
        return jax.ShapeDtypeStruct((n,) + tail, dtype)

    return custom_call_sites(
        frontend.decode_program(), eng.cache, eng.variables,
        slot(jnp.int32), slot(jnp.bool_), slot(jnp.int32),
        slot(eng.rng.dtype, *eng.rng.shape), slot(jnp.int32))


@contextlib.contextmanager
def serving(engine, sizes: ServeSizes):
    """The stack a deployment runs: frontend + background pump + HTTP door
    (and a client for it), all in this process; torn down in order."""
    from apex_tpu.serving import (HttpReplicaClient, HttpServingServer,
                                  ServingFrontend)

    frontend = ServingFrontend(engine)
    frontend.start()
    server = HttpServingServer(frontend).start()
    client = HttpReplicaClient("127.0.0.1", server.port)
    try:
        yield frontend, client
    finally:
        client.shutdown(sizes.stream_timeout_s)
        server.shutdown(sizes.stream_timeout_s)
        frontend.shutdown(sizes.stream_timeout_s)


def run_pass(frontend, client, prompts, sizes: ServeSizes, id0: int):
    """Submit one pass of the traffic wave by wave; returns every stream's
    generated tokens.  A stream that does not finish raises."""
    import numpy as np

    from apex_tpu.serving import Request

    traffic = sizes.traffic
    handles = {}
    for wave in sorted({s.wave for s in traffic}):
        if wave:
            first_header = next(i for i, s in enumerate(traffic)
                                if s.header and s.wave < wave)
            handles[first_header].result(timeout=sizes.stream_timeout_s)
        for i, (spec, prompt) in enumerate(zip(traffic, prompts)):
            if spec.wave != wave:
                continue
            door = client if spec.http else frontend
            handles[i] = door.submit(
                Request(prompt=prompt, max_new_tokens=spec.new_tokens),
                request_id=id0 + i)
    outs = [np.asarray(handles[i].result(timeout=sizes.stream_timeout_s),
                       np.int32) for i in range(len(traffic))]
    for spec, out in zip(traffic, outs):
        if out.shape != (spec.new_tokens,):
            raise AssertionError(
                f"stream returned {out.shape[0]} tokens, "
                f"asked for {spec.new_tokens}")
    return outs


def check_pool_drained(engine) -> dict:
    """After a drain the free stack and the radix cache partition the
    pool and no page has a reader."""
    import jax.numpy as jnp

    from apex_tpu.serving import kv_pool

    cache = engine.cache
    usable = cache["free_stack"].shape[0] - 1
    free = int(kv_pool.free_page_count(cache))
    refs = int(jnp.sum(cache["page_ref"]))
    cached = len(engine.prefix)
    if refs != 0 or free != usable - cached:
        raise AssertionError(
            f"pool leaked: page_ref.sum()={refs}, free={free}, "
            f"usable={usable}, radix pages={cached}")
    return {"usable_pages": usable, "free_pages": free,
            "radix_pages": cached}


class GreedyReference:
    """Lock-step ``generate`` and teacher-forced logits of one model, each
    jitted once per shape."""

    def __init__(self, model, variables):
        self.model = model
        self.variables = variables
        self._generate = {}
        self._logits = {}

    def generate(self, prompt, new_tokens: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from apex_tpu.models.generation import generate

        key = (len(prompt), new_tokens)
        if key not in self._generate:
            self._generate[key] = jax.jit(
                lambda v, p: generate(self.model, v, p, new_tokens))
        out = self._generate[key](self.variables, jnp.asarray(prompt)[None])
        return np.asarray(out)[0, len(prompt):]

    def near_tie_excess(self, prompt, got) -> float:
        """How far ``got`` is from a greedy stream of this model, in units
        of the near-tie tolerance: the largest, over positions, of
        (max logit - logit of the emitted token) / tolerance, with the
        logits teacher-forced on ``got`` itself.  <= 1 passes."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        ids = np.concatenate([prompt, got[:-1]]).astype(np.int32)
        if len(ids) not in self._logits:
            self._logits[len(ids)] = jax.jit(
                lambda v, x: self.model.apply(v, x).astype(jnp.float32))
        logits = np.asarray(
            self._logits[len(ids)](self.variables, jnp.asarray(ids)[None])
        )[0, len(prompt) - 1:]
        margin = logits.max(-1) - logits[np.arange(len(got)), got]
        tol = NEAR_TIE_REL * np.abs(logits).max(-1)
        return float((margin / tol).max())


def check_streams(reference: GreedyReference, prompts, outs, expected,
                  what: str) -> dict:
    """Every stream equals ``expected`` token for token, or — where bf16
    arithmetic reordered a near-tie — is still a greedy stream of the
    reference model within :data:`NEAR_TIE_REL`."""
    import numpy as np

    identical = near_ties = 0
    wrong = []
    for i, (prompt, out, exp) in enumerate(zip(prompts, outs, expected)):
        if np.array_equal(out, exp):
            identical += 1
            continue
        at = int(np.argmax(out != exp))
        excess = reference.near_tie_excess(prompt, out)
        if excess > 1.0:
            wrong.append(
                f"request {i}: output diverges from {what} at token {at} "
                f"({out[at]} vs {exp[at]}) and is not a near-tie: "
                f"{excess:.1f}x the bf16 tolerance")
            continue
        near_ties += 1
        log(f"  request {i}: differs from {what} from token {at} on, a "
            f"bf16 near-tie ({excess:.2f} of the tolerance)")
    if wrong:
        raise AssertionError("; ".join(wrong))
    return {"streams": len(outs), "identical": identical,
            "near_ties": near_ties}


def gpt_weights(cfg, seed: int):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel

    model = GPTModel(cfg)
    variables = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                    jnp.zeros((1, 8), jnp.int32))
    return model, variables


def serve_setup(cfg, sizes: ServeSizes, seed: int):
    """Weights, the two passes of prompts and the engine settings that the
    one-chip and the tensor-parallel phase share."""
    model, variables = gpt_weights(cfg, seed)
    passes = [build_requests(seed + n, cfg.vocab_size, sizes)
              for n in range(2)]
    engine_kw = dict(num_slots=sizes.num_slots, page_size=sizes.page_size,
                     num_pages=pool_pages(cfg, sizes),
                     sync_every=sizes.sync_every, prefix_cache=True)
    log(f"{cfg.num_layers} layers x hidden {cfg.hidden_size}, "
        f"{len(sizes.traffic)} requests x 2 passes over {sizes.num_slots} "
        f"slots, pool {engine_kw['num_pages']} pages of {sizes.page_size} "
        f"tokens ({sizes.pool_bytes / 2 ** 30:.2f} GiB)")
    return model, variables, passes, engine_kw


def drive_engine(engine, passes, sizes: ServeSizes, label: str) -> dict:
    """Two passes of the traffic through one long-lived server over
    ``engine``; returns outputs, stats and the decode chunk's kernel count.
    The second pass has fresh tokens of the same shapes, so the first
    pass's radix pages cannot change its admission compile keys: it must
    add no compile."""
    t0, compile_s0 = time.perf_counter(), compile_seconds()
    with serving(engine, sizes) as (frontend, client):
        kernel_sites = decode_chunk_kernel_sites(frontend)
        outs, compiles, walls = [], [], []
        for n, prompts in enumerate(passes):
            t_pass = time.perf_counter()
            outs.append(run_pass(frontend, client, prompts, sizes,
                                 id0=n * len(prompts)))
            walls.append(time.perf_counter() - t_pass)
            compiles.append(frontend.stats()["jit.compiles"])
    stats = frontend.stats()
    pool = check_pool_drained(engine)
    log(f"{label}: decode chunk has {kernel_sites} tpu_custom_call sites; "
        f"pass walls {[round(w, 2) for w in walls]} s (the first one "
        f"compiles), jit.compiles after each pass {compiles}, "
        f"decode_steps {stats['decode_steps']}, tokens out "
        f"{sum(len(o) for p in outs for o in p)}, prefix_hits "
        f"{stats['prefix_hits']}, pool {pool}, compile seconds "
        f"{compile_seconds() - compile_s0:.1f}, wall seconds "
        f"{time.perf_counter() - t0:.1f}")
    if compiles[0] <= 0:
        raise AssertionError("the compile watcher saw no compile")
    if compiles[-1] != compiles[0]:
        raise AssertionError(
            f"the second pass compiled {compiles[-1] - compiles[0]} "
            f"programs; a warm server must compile none")
    if stats["prefix_hits"] < sum(s.wave > 0 and s.header
                                  for s in sizes.traffic):
        raise AssertionError(
            f"prefix_hits {stats['prefix_hits']}: the later wave did not "
            f"hit the header its first wave cached")
    if stats["retired"] != len(passes) * len(sizes.traffic):
        raise AssertionError(f"retired {stats['retired']} streams")
    return {"outs": outs, "stats": stats, "pool": pool,
            "kernel_sites": kernel_sites, "compiles": compiles}


def serve_phase(cfg, sizes: ServeSizes, *, seed: int) -> dict:
    from apex_tpu.serving import PagedDecodeEngine

    model, variables, passes, engine_kw = serve_setup(cfg, sizes, seed)
    report = drive_engine(PagedDecodeEngine(model, variables, **engine_kw),
                          passes, sizes, "serve")
    reference = GreedyReference(model, variables)
    t0 = time.perf_counter()
    for n, (prompts, outs) in enumerate(zip(passes, report["outs"])):
        expected = [reference.generate(p, s.new_tokens)
                    for p, s in zip(prompts, sizes.traffic)]
        verdict = check_streams(reference, prompts, outs, expected,
                                "lock-step generate")
        log(f"serve: pass {n}: {verdict['identical']}/{verdict['streams']} "
            f"streams token-identical to lock-step generate, "
            f"{verdict['near_ties']} bf16 near-ties")
        report[f"pass{n}"] = verdict
    log(f"serve: reference took {time.perf_counter() - t0:.1f} s")
    return report


def tp_serve_phase(cfg, sizes: ServeSizes, *, seed: int, tp: int) -> dict:
    """The traffic through ``TensorParallelPagedEngine`` on ``tp`` chips,
    stream by stream against the one-chip engine on device 0."""
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import (PagedDecodeEngine,
                                  TensorParallelPagedEngine)
    from apex_tpu.serving.tp import shard_model_variables, tp_mesh

    model, variables, passes, engine_kw = serve_setup(cfg, sizes, seed)
    one_chip = drive_engine(PagedDecodeEngine(model, variables, **engine_kw),
                            passes, sizes, "one-chip engine")

    tp_cfg = dataclasses.replace(cfg, tensor_parallel_size=tp)
    tp_model = GPTModel(tp_cfg)
    mesh = tp_mesh(tp)
    tp_variables, _ = shard_model_variables(tp_model, variables, mesh)
    engine = TensorParallelPagedEngine(tp_model, tp_variables, mesh=mesh,
                                       **engine_kw)
    pool = engine.cache["layers"][0]
    placement = {}
    for name in ("k_pages", "v_pages"):
        shards = pool[name].addressable_shards
        devices = {s.device for s in shards}
        nbytes = {s.data.nbytes for s in shards}
        if len(devices) != tp or nbytes != {pool[name].nbytes // tp}:
            raise AssertionError(
                f"pool {name} is not split evenly over {tp} chips: "
                f"{len(devices)} devices, shard bytes {sorted(nbytes)} of "
                f"{pool[name].nbytes}")
        placement[name] = sorted(str(d) for d in devices)
    log(f"tp_serve: pool K/V of layer 0 on {placement['k_pages']}, "
        f"1/{tp} of {pool['k_pages'].nbytes} bytes each")
    report = drive_engine(engine, passes, sizes, f"tp={tp} engine")
    if report["stats"]["tp_world"] != tp:
        raise AssertionError(f"tp_world {report['stats']['tp_world']}")
    reference = GreedyReference(model, variables)
    for n, prompts in enumerate(passes):
        verdict = check_streams(reference, prompts, report["outs"][n],
                                one_chip["outs"][n], "the one-chip engine")
        log(f"tp_serve: pass {n}: {verdict['identical']}/"
            f"{verdict['streams']} streams token-identical to the one-chip "
            f"engine, {verdict['near_ties']} bf16 near-ties")
        report[f"pass{n}"] = verdict
    return report


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def train_loop(cfg, sizes: TrainSizes, *, seed: int, mesh=None) -> dict:
    """``sizes.steps`` steps of ``make_pretrain_step`` + ``FusedLAMB.step``
    on one repeated synthetic batch; with ``mesh`` the batch is split over
    its ``data`` axis and the grads are averaged across it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.mesh import DATA_AXIS
    from apex_tpu.models import (BertForPreTraining, make_pretrain_step,
                                 synthetic_batch)
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.parallel import DistributedDataParallel

    model = BertForPreTraining(cfg)
    batch = synthetic_batch(np.random.default_rng(seed), cfg,
                            sizes.batch_size, sizes.seq_len)
    params = jax.jit(lambda key: model.init(
        key, batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"])["params"])(jax.random.PRNGKey(seed))
    step = grad_step = make_pretrain_step(model)
    if mesh is not None:
        # Mosaic kernels cannot be partitioned automatically: the
        # data-parallel step is a shard_map with the explicit reduction
        ddp = DistributedDataParallel(model)

        def dp_step(p, b, s):
            loss, grads = grad_step(p, b, s)
            return lax.pmean(loss, DATA_AXIS), ddp.allreduce_gradients(grads)

        step = jax.jit(jax.shard_map(
            dp_step, mesh=mesh, in_specs=(P(), P(DATA_AXIS), P()),
            out_specs=P(), check_vma=False))
        batch = jax.device_put(batch, NamedSharding(mesh, P(DATA_AXIS)))
        params = jax.device_put(params, NamedSharding(mesh, P()))
    opt = FusedLAMB(
        params, lr=sizes.lr, weight_decay=0.01,
        exclude_from_weight_decay=lambda n: "bias" in n or "norm" in n.lower())
    n_params = sum(x.size for x in jax.tree.leaves(params))
    watched = [0, len(jax.tree.leaves(params)) // 2, -1]
    before = [np.asarray(jax.tree.leaves(params)[i]) for i in watched]
    # the same dropout seed every step: the losses compare like with like
    dropout_seed = jnp.int32(seed)

    t0 = time.perf_counter()
    compiled = step.lower(params, batch, dropout_seed).compile()
    hlo = compiled.as_text()
    compile_s = time.perf_counter() - t0
    losses, step_s = [], []
    for i in range(sizes.steps):
        t0 = time.perf_counter()
        loss, grads = compiled(params, batch, dropout_seed)
        params = opt.step(grads)
        jax.block_until_ready((loss, params))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    hyper = {k: jnp.float32(v) for k, v in opt.defaults.items()
             if isinstance(v, (int, float))}
    opt_sites = custom_call_sites(
        opt._jit_step, grads, opt.master, opt.state, opt.step_count, hyper,
        jnp.float32(1.0), jnp.float32(0.0), None, opt.wd_per_segment)

    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a repeated batch: "
                             f"{losses}")
    after = [np.asarray(jax.tree.leaves(params)[i]) for i in watched]
    if any(np.array_equal(a, b) for a, b in zip(before, after)):
        raise AssertionError("a watched parameter did not change")
    return {"losses": losses, "compile_s": compile_s, "step_s": step_s,
            "n_params": n_params, "hlo": hlo, "optimizer_sites": opt_sites,
            "kernel_sites": hlo.count("tpu_custom_call"),
            "batch": batch, "params": params}


def train_phase(cfg, sizes: TrainSizes, *, seed: int) -> dict:
    log(f"train: {cfg.num_layers} layers x hidden {cfg.hidden_size}, batch "
        f"{sizes.batch_size} x seq {sizes.seq_len}, {sizes.steps} steps of "
        f"FusedLAMB at lr {sizes.lr}")
    report = train_loop(cfg, sizes, seed=seed)
    log(f"train: {report['n_params'] / 1e6:.1f}M params; grad step has "
        f"{report['kernel_sites']} tpu_custom_call sites (compiled in "
        f"{report['compile_s']:.1f} s), optimizer step "
        f"{report['optimizer_sites']}")
    log(f"train: losses {[round(x, 4) for x in report['losses']]}")
    log(f"train: smoke step seconds {[round(s, 3) for s in report['step_s']]}"
        f" (the first includes the optimizer's compile)")
    return report


def dp_train_phase(cfg, sizes: TrainSizes, *, seed: int, dp: int) -> dict:
    """The step data-parallel over ``dp`` chips against the same global
    batch on one chip.  Dropout is off on both sides: its mask depends on
    how the batch is split, and this compares the arithmetic."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from apex_tpu.mesh import DATA_AXIS

    cfg = dataclasses.replace(cfg, hidden_dropout=0.0, attention_dropout=0.0)
    sizes = dataclasses.replace(sizes, steps=3)
    log(f"dp_train: batch {sizes.batch_size} ({sizes.batch_size // dp} per "
        f"chip) x seq {sizes.seq_len}, {sizes.steps} steps, dropout off")
    one_chip = train_loop(cfg, sizes, seed=seed)
    log(f"dp_train: one chip losses {one_chip['losses']}")
    one_chip_losses = one_chip["losses"]
    del one_chip
    gc.collect()            # its params and optimizer state leave device 0
    mesh = Mesh(np.asarray(jax.devices()[:dp]), (DATA_AXIS,))
    report = train_loop(cfg, sizes, seed=seed, mesh=mesh)
    log(f"dp_train: dp={dp} losses {report['losses']}; grad step has "
        f"{report['kernel_sites']} tpu_custom_call sites, "
        f"{report['hlo'].count('all-reduce')} all-reduce mentions")
    ids = report["batch"]["input_ids"]
    shard_devices = {s.device for s in ids.addressable_shards}
    shard_rows = {s.data.shape[0] for s in ids.addressable_shards}
    if len(shard_devices) != dp or shard_rows != {sizes.batch_size // dp}:
        raise AssertionError(
            f"batch not split over {dp} chips: {len(shard_devices)} "
            f"devices, rows per shard {sorted(shard_rows)}")
    leaf = jax.tree.leaves(report["params"])[0]
    if not (leaf.sharding.is_fully_replicated
            and len(leaf.sharding.device_set) == dp):
        raise AssertionError(f"params not replicated over {dp} chips: "
                             f"{leaf.sharding}")
    if "all-reduce" not in report["hlo"]:
        raise AssertionError("no all-reduce in the data-parallel grad step")
    np.testing.assert_allclose(
        report["losses"], one_chip_losses, rtol=DP_LOSS_RTOL,
        err_msg="data-parallel losses left the one-chip run")
    log(f"dp_train: losses agree with one chip within rtol {DP_LOSS_RTOL} "
        f"(largest relative gap "
        f"{np.max(np.abs(np.divide(report['losses'], one_chip_losses) - 1)):.2e}"
        f"); batch on {len(shard_devices)} chips, params replicated")
    report["one_chip_losses"] = one_chip_losses
    return report


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def require_kernels(report: dict, what: str) -> None:
    sites = [report["kernel_sites"], report.get("optimizer_sites", 1)]
    if min(sites) <= 0:
        raise AssertionError(
            f"{what}: no tpu_custom_call in the compiled program — the "
            f"Pallas kernels went through the interpreter or a reference")


def main(argv=None) -> int:
    args = parse_args(argv)
    phases = selected_phases(args)
    need = 4 if args.multichip else 1

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); jax found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind}). It does not run on the CPU.",
              flush=True)
        return 1

    import jax.numpy as jnp

    from apex_tpu.models import bert_large_config
    from apex_tpu.models.gpt import gpt2_small_config
    from apex_tpu.obs import compile_watch
    from apex_tpu.utils.compile_cache import enable_compile_cache

    compile_watch.watcher()             # counts every compile from here on

    log(f"device: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); jax {jax.__version__}; phases "
        f"{', '.join(phases)}; seed {args.seed}; compile cache at "
        f"{enable_compile_cache()}")
    gpt_cfg = gpt2_small_config(dtype=jnp.bfloat16)
    bert_cfg = bert_large_config()
    t_all = time.perf_counter()
    for phase in phases:
        t0 = time.perf_counter()
        if phase == "serve":
            report = serve_phase(gpt_cfg, ServeSizes(), seed=args.seed)
        elif phase == "train":
            report = train_phase(bert_cfg, TrainSizes(), seed=args.seed)
        elif phase == "tp_serve":
            report = tp_serve_phase(gpt_cfg, ServeSizes(), seed=args.seed,
                                    tp=need)
        else:
            report = dp_train_phase(bert_cfg, TrainSizes(), seed=args.seed,
                                    dp=need)
        require_kernels(report, phase)
        log(f"{phase}: passed in {time.perf_counter() - t0:.1f} s")
        del report
        gc.collect()        # the phase's buffers leave the device
    log(f"all phases passed in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
