"""On-chip flash-attention block-size autotune (VERDICT r4 ask #2).

Sweeps (block_q, block_k) for the flagship attention shapes (BERT-Large:
b=8, h=16, s=512, d=64 bf16; GPT/Llama long-seq variants) timing one
fwd+bwd step per candidate, and — when run on a real TPU — writes the
winners to ``apex_tpu/ops/_flash_block_table.json``, which
``flash_attention._block_sizes`` consults at trace time. Also times the
tight-head-dim layout (``flash_attention._TIGHT_HEADDIM``) against the
128-padded default at the winning block config.

Every timing runs in a child process, one at a time; the parent never
imports jax, so it never holds the chip a child needs.
    python tpu_autotune.py            # full sweep + table write
    python tpu_autotune.py --child --shape 8,16,512,64 --tight 0 \
        --candidates "128,128;256,128" # one timing subprocess (internal)

Prints one summary JSON line to stdout at the end; diagnostics to stderr.
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TABLE_PATH = os.path.join(REPO, "apex_tpu", "ops", "_flash_block_table.json")

# flagship shapes (batch, heads, seq, head_dim) — BERT-Large attention is
# the bench gate; 1024/2048 cover GPT/Llama blocks at the same head dim
SHAPES = [(8, 16, 512, 64), (4, 16, 1024, 64), (2, 16, 2048, 64)]
CANDS = [(bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512)]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _child(shape, tight, candidates):
    """Time fwd+bwd for each (bq, bk) at one shape; print a JSON line."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"tpu_autotune: times kernels on a TPU; jax found "
                 f"{dev.platform} ({dev.device_kind})")
    enable_compile_cache()
    fa_impl = importlib.import_module("apex_tpu.ops.flash_attention")
    fa_impl._TIGHT_HEADDIM = bool(tight)    # before anything is traced
    flash_attention = fa_impl.flash_attention

    b, h, s, d = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)

    results = {}
    for bq, bk in candidates:
        if bq > s or bk > s:
            continue

        def loss(q, k, v, bq=bq, bk=bk):
            o = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        try:
            out = step(q, k, v)
            jax.block_until_ready(out)
        except Exception as e:  # noqa: BLE001 — illegal layout for this chip
            log(f"  ({bq},{bk}) failed: {type(e).__name__}: {str(e)[:120]}")
            continue
        t0 = time.perf_counter()
        for _ in range(10):
            out = step(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 10
        results[f"{bq},{bk}"] = dt * 1e3
        log(f"  ({bq},{bk}) {dt*1e3:.3f} ms")
    print(json.dumps({"shape": list(shape), "tight": tight,
                      "platform": dev.platform,
                      "device_kind": dev.device_kind, "ms": results}))


def _run_child(shape, tight, candidates, timeout=1500):
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--shape", ",".join(map(str, shape)), "--tight", str(int(tight)),
           "--candidates", ";".join(f"{a},{b}" for a, b in candidates)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                       cwd=REPO)
    sys.stderr.write(r.stderr[-2000:])
    if r.returncode:
        raise RuntimeError(f"timing child exited {r.returncode}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--shape", type=str, default="")
    ap.add_argument("--tight", type=int, default=0)
    ap.add_argument("--candidates", type=str, default="")
    args = ap.parse_args()

    if args.child:
        shape = tuple(int(x) for x in args.shape.split(","))
        cands = [tuple(int(x) for x in c.split(","))
                 for c in args.candidates.split(";") if c]
        _child(shape, bool(args.tight), cands)
        return

    table = {}
    summary = {"metric": "flash_block_autotune", "shapes": {}}
    for shape in SHAPES:
        b, h, s, d = shape
        log(f"shape b={b} h={h} s={s} d={d}:")
        res = _run_child(shape, tight=False, candidates=CANDS)
        summary["platform"] = res["platform"]
        summary["device_kind"] = res["device_kind"]
        if not res["ms"]:
            log("  no candidate compiled; skipping shape")
            continue
        best = min(res["ms"], key=res["ms"].get)
        default_ms = res["ms"].get("128,128")
        best_ms = res["ms"][best]
        bq, bk = (int(x) for x in best.split(","))
        table[f"{s},{s},{d},bfloat16"] = [bq, bk]
        gain = (default_ms / best_ms - 1.0) * 100 if default_ms else 0.0
        log(f"  WINNER ({bq},{bk}) {best_ms:.3f} ms "
            f"({gain:+.1f}% vs 128,128 default)")
        entry = {"winner": [bq, bk], "ms": res["ms"],
                 "gain_vs_default_pct": round(gain, 1)}
        # tight-head-dim at the winning blocks (d=64: half the MXU padding)
        tight_res = _run_child(shape, tight=True, candidates=[(bq, bk)])
        if tight_res["ms"]:
            tms = tight_res["ms"][best]
            entry["tight_headdim_ms"] = tms
            entry["tight_speedup"] = round(best_ms / tms, 3)
            log(f"  tight-head-dim {tms:.3f} ms "
                f"({best_ms / tms:.2f}x vs padded)")
        summary["shapes"]["x".join(map(str, shape))] = entry

    if not table:
        sys.exit("tpu_autotune: nothing measured; table NOT written")
    with open(TABLE_PATH, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    log(f"wrote {TABLE_PATH}")
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
