"""On-chip profiler evidence for docs/performance.md (VERDICT r4 ask #3).

Two artifacts:

1. **Step breakdown** — traces 3 BERT-Large bench steps with
   ``jax.profiler.trace`` on the real chip, parses the trace-event JSON,
   and aggregates device time by op category (fusions, dots/convs,
   Pallas custom-calls, collectives, copies, host). This replaces the
   design-intent claims about where the step time goes with measurement.

2. **Overlap scheduling proof** — AOT-compiles the data-parallel (dp=8)
   BERT step AND a ZeRO-sharded optimizer step for an 8-chip TPU topology
   (no 8 chips needed — compile only) and scans the optimized HLO for
   async collective pairs (``all-gather-start``/``-done``,
   ``all-reduce-start``/``-done``) with independent compute scheduled
   between start and done: the TPU compiler's own schedule either does or
   does not overlap the ZeRO all-gather / grad all-reduce with compute
   (SURVEY hard part #5).

Writes ``PROFILE_<tag>.json`` + prints one summary JSON line.
"""

import collections
import glob
import gzip
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# 1. trace + parse
# ---------------------------------------------------------------------------

CATEGORIES = [
    ("collective", re.compile(
        r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")),
    ("pallas", re.compile(r"custom-call|tpu_custom_call")),
    ("dot", re.compile(r"dot|conv")),
    ("fusion", re.compile(r"fusion")),
    ("copy", re.compile(r"copy|transpose|reshape|bitcast")),
]


def categorize(name: str) -> str:
    low = name.lower()
    for cat, pat in CATEGORIES:
        if pat.search(low):
            return cat
    return "other"


def parse_trace(logdir):
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # device-side events live on pids whose process name mentions TPU/device
    pid_names = {e["pid"]: e.get("args", {}).get("name", "")
                 for e in events if e.get("ph") == "M"
                 and e.get("name") == "process_name"}
    device_pids = {p for p, n in pid_names.items()
                   if re.search(r"tpu|device|/device:", n, re.I)}
    by_cat = collections.Counter()
    by_name = collections.Counter()
    t_min, t_max = None, None
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        dur = e["dur"]  # microseconds
        name = e.get("name", "")
        by_cat[categorize(name)] += dur
        by_name[re.sub(r"[.\d]+$", "", name)[:60]] += dur
        ts = e.get("ts", 0)
        t_min = ts if t_min is None else min(t_min, ts)
        t_max = max(t_max or 0, ts + dur)
    span = (t_max - t_min) if t_min is not None else 0
    return {"device_time_us_by_category": dict(by_cat),
            "top_ops_us": dict(by_name.most_common(15)),
            "span_us": span,
            "trace_file": os.path.relpath(paths[-1], REPO)}


def run_traced_steps(steps=3):
    """Build the bench train step once, warm it up OUTSIDE the tracer (the
    15-min first compile must not land in the trace), then trace ``steps``
    steady-state steps."""
    import jax
    import numpy as np

    from apex_tpu.models import (BertForPreTraining, bert_large_config,
                                 make_pretrain_step, synthetic_batch)
    from apex_tpu.optimizers import FusedLAMB

    devs = jax.devices()
    if devs[0].platform == "cpu":
        log("CPU backend: tracing anyway (smoke), numbers meaningless")
    cfg = bert_large_config()
    model = BertForPreTraining(cfg)
    rng = np.random.default_rng(0)
    batch = synthetic_batch(rng, cfg, 8, 512)
    log("init params...")
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"],
                        batch["token_type_ids"],
                        batch["attention_mask"])["params"]
    step = make_pretrain_step(model)
    opt = FusedLAMB(params, lr=1e-4, weight_decay=0.01)

    def train_step(p, i):
        loss, grads = step(p, batch, i)
        return loss, opt.step(grads)

    log("compile + warmup...")
    t0 = time.perf_counter()
    loss, params = train_step(params, 0)
    jax.block_until_ready(params)
    log(f"compiled in {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    loss, params = train_step(params, 1)
    jax.block_until_ready(params)
    step_ms = (time.perf_counter() - t0) * 1e3

    logdir = os.path.join(REPO, "profile_trace")
    with jax.profiler.trace(logdir):
        for i in range(steps):
            loss, params = train_step(params, 2 + i)
        jax.block_until_ready(params)
    parsed = parse_trace(logdir)
    parsed["steps_traced"] = steps
    parsed["step_ms_untraced"] = round(step_ms, 2)
    return parsed


# ---------------------------------------------------------------------------
# 2. AOT overlap-scheduling proof
# ---------------------------------------------------------------------------

def _sync_collective_report(hlo_text: str, max_items: int = 24):
    """Schedulable-overlap evidence for XLA:TPU's SYNC-form HLO.

    This XLA version's TPU pipeline keeps collectives synchronous in the
    final HLO (``all-reduce``, not ``-start/-done``) — asyncification is
    performed later by the backend's latency-hiding scheduler and never
    appears in module text (GPU is where start/done pairs show up). What CAN
    be proven at the HLO level is *schedulability*: for each collective, the
    number of independent ops (and compute ops) between it and its first
    consumer in program order — the window the scheduler can hide the
    collective behind. Also records the backend's chosen collective
    algorithm (ring strategy etc.) when present.
    """
    lines = [ln.strip() for ln in hlo_text.splitlines()]
    kinds = re.compile(
        r"%?([\w.-]+) = \S+ (all-reduce|all-gather|reduce-scatter|"
        r"collective-permute|all-to-all)\(")
    out = []
    for i, ln in enumerate(lines):
        m = kinds.match(ln)
        if not m or "-start" in ln or "-done" in ln:
            continue
        name, kind = m.group(1), m.group(2)
        strat = re.search(r'"strategy":"(\w+)"', ln)
        use_pat = re.compile(r"[(,]\s*%" + re.escape(name) + r"[),]")
        first_use = None
        between, compute = 0, 0
        for j in range(i + 1, len(lines)):
            if use_pat.search(lines[j]):
                first_use = j
                break
            if re.search(r" = ", lines[j]) and not re.search(
                    r"parameter|constant", lines[j]):
                between += 1
                if re.search(r"fusion|dot|convolution|custom-call",
                             lines[j]):
                    compute += 1
        out.append({"kind": kind,
                    "algorithm": strat.group(1) if strat else None,
                    "ops_to_first_use": between if first_use else None,
                    "compute_to_first_use": compute if first_use else None})
        if len(out) >= max_items:
            break
    return out


def _async_overlap_report(hlo_text: str):
    """For each async collective pair, count non-trivial ops scheduled
    between start and done in the entry computation's program order."""
    lines = [ln.strip() for ln in hlo_text.splitlines()]
    starts = {}
    pairs = []
    for i, ln in enumerate(lines):
        m = re.match(r"%?([\w.-]+) = .*(all-gather-start|all-reduce-start|"
                     r"reduce-scatter-start|collective-permute-start|"
                     r"async-start)", ln)
        if m:
            starts[m.group(1)] = (i, m.group(2))
            continue
        m2 = re.search(r"(all-gather-done|all-reduce-done|"
                       r"reduce-scatter-done|collective-permute-done|"
                       r"async-done)[(]%?([\w.-]+)", ln)
        if m2 and m2.group(2) in starts:
            s_line, kind = starts.pop(m2.group(2))
            between = [x for x in lines[s_line + 1:i]
                       if re.search(r" = ", x)
                       and not re.search(r"-(start|done)|parameter|constant",
                                         x)]
            compute = [x for x in between
                       if re.search(r"fusion|dot|convolution|custom-call", x)]
            pairs.append({"kind": kind.replace("-start", ""),
                          "ops_between": len(between),
                          "compute_between": len(compute)})
    return pairs


def aot_overlap_check():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # no jax.devices() here: this half only compiles, for the topology
    # tpu_aot describes (the name lives there, in one place)
    from tpu_aot import _topology

    topo = _topology()
    mesh = topologies.make_mesh(topo, (8,), ("data",))
    from apex_tpu.ops._dispatch import forced_mosaic

    out = {"available": True, "topology": str(topo)}
    with forced_mosaic():
        try:
            out["dp8_grad_allreduce_pairs"] = _dp8_overlap_hlo(mesh)
        except Exception as e:  # noqa: BLE001
            out["dp8_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        try:
            out["zero_shard_step_pairs"] = _zero_overlap_hlo(mesh)
        except Exception as e:  # noqa: BLE001
            out["zero_shard_step_error"] = \
                f"{type(e).__name__}: {str(e)[:200]}"
    return out


def _dp8_overlap_hlo(mesh):
    """AOT-compile the dp=8 BERT-Large grad step (shard_map with an explicit
    grad pmean — plain jit cannot auto-partition the Mosaic kernels) and
    report whether the compiler overlaps the grad all-reduce with backward
    compute (SURVEY hard part #5)."""
    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.models import (BertForPreTraining, bert_large_config,
                                 make_pretrain_step, synthetic_batch)

    cfg = bert_large_config()
    model = BertForPreTraining(cfg)
    rng = np.random.default_rng(0)
    batch = synthetic_batch(rng, cfg, 8, 512)
    step = make_pretrain_step(model)
    abstract_params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch["input_ids"],
                           batch["token_type_ids"],
                           batch["attention_mask"])["params"])

    def dp_step(p, b, i):
        loss, grads = step(p, b, i)
        grads = jax.tree.map(lambda g: lax.pmean(g, "data"), grads)
        return lax.pmean(loss, "data"), grads

    fn = jax.shard_map(dp_step, mesh=mesh, in_specs=(P(), P("data"), P()),
                       out_specs=(P(), P()), check_vma=False)

    repl = NamedSharding(mesh, P())
    data_sh = {k: NamedSharding(mesh, P("data", *[None] * (v.ndim - 1)))
               for k, v in batch.items()}
    params_in = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        abstract_params)
    batch_in = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                        sharding=data_sh[k])
                for k, v in batch.items()}
    i_in = jax.ShapeDtypeStruct((), np.int32, sharding=repl)
    hlo = jax.jit(fn).lower(params_in, batch_in, i_in).compile().as_text()
    return {"async_pairs": _async_overlap_report(hlo),
            "sync_collectives": _sync_collective_report(hlo)}


def _zero_overlap_hlo(mesh):
    """AOT-compile the ZeRO shard_step (psum_scatter -> local update ->
    param all-gather) for the 8-chip topology and report whether the TPU
    scheduler overlaps the param all-gather with independent work
    (docs/performance.md's ZeRO claim; SURVEY hard part #5)."""
    import unittest.mock as mock

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    params = {"w1": np.zeros((1024, 1024), np.float32),
              "w2": np.zeros((4096, 1024), np.float32),
              "emb": np.zeros((8192, 1024), np.float32),
              "b": np.zeros((1024,), np.float32)}
    # the ctor device_puts master/state onto the mesh — impossible on a
    # device-less topology; shapes are all the lowering needs
    with mock.patch.object(jax, "device_put", lambda x, s=None: x):
        opt = DistributedFusedAdam(params, lr=1e-3, weight_decay=0.01,
                                   mesh=mesh, dp_axis="data")
    row = P("data", None)
    state_specs = {k: row for k in opt.state}

    def body(g, master, state, step):
        p, m2, s2, st2, _ = opt.shard_step(g, master, state, step)
        return p, m2, s2, st2

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), row, state_specs, P()),
                       out_specs=(P(), row, state_specs, P()),
                       check_vma=False)

    def spec(shape, sh):
        return jax.ShapeDtypeStruct(shape, np.float32,
                                    sharding=NamedSharding(mesh, sh))

    g_in = jax.tree.map(lambda a: spec(a.shape, P()), params)
    master_in = spec(opt.master.shape, row)
    state_in = {k: spec(v.shape, row) for k, v in opt.state.items()}
    step_in = jax.ShapeDtypeStruct((), np.int32,
                                   sharding=NamedSharding(mesh, P()))
    hlo = jax.jit(fn).lower(g_in, master_in, state_in,
                            step_in).compile().as_text()
    return {"async_pairs": _async_overlap_report(hlo),
            "sync_collectives": _sync_collective_report(hlo)}


def main():
    from apex_tpu.utils.compile_cache import enable_compile_cache

    log(f"compilation cache: {enable_compile_cache()}")
    tag = os.environ.get("APEX_TPU_TAG", "session")
    out = {"metric": "tpu_profile", "tag": tag}
    try:
        out["step_breakdown"] = run_traced_steps()
    except Exception as e:  # noqa: BLE001
        import traceback

        log(traceback.format_exc())
        out["step_breakdown_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    try:
        out["aot_overlap"] = aot_overlap_check()
    except Exception as e:  # noqa: BLE001
        import traceback

        log(traceback.format_exc())
        out["aot_overlap_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    path = os.path.join(REPO, f"PROFILE_{tag}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    log(f"wrote {path}")
    print(json.dumps({k: v for k, v in out.items()
                      if not isinstance(v, dict)} |
                     {"wrote": os.path.basename(path),
                      "ok": "step_breakdown" in out}))


if __name__ == "__main__":
    main()
