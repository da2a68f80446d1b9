#!/usr/bin/env python3
"""Readings for a serving cell's limit where the family's reference can
stand in for more than the float8 control (``references.<family>.VARIANTS``:
faults of the program's own mathematics), in one process on the chip.

For each seed: one run of the cell at its own load (the runner itself),
whose sampled requests the reference judges (the program's reading); for the
first seeds also each variant in the reference's place: at each position of
the same prompts and tokens, the gap of the token that variant puts first.

    python benchmark/proof/serve_variants.py <cell> <seconds> <seeds> <variant seeds> <variant> [<variant> ...]
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def quantiles(token_gaps) -> dict:
    """What a look at the statistic needs of the per-token gaps."""
    import numpy as np

    g = np.sort(np.concatenate(token_gaps))
    at = lambda q: float(g[min(len(g) - 1, int(q * len(g)))])
    return {"tokens": len(g), "max": float(g[-1]), "p99": at(0.99),
            "p95": at(0.95), "p90": at(0.9), "p75": at(0.75),
            "p50": at(0.5), "mean": float(g.mean()),
            "nonzero": float((g > 0).mean())}


def main(cell_name: str, seconds: float, n_program: int, n_variant: int,
         variants: list) -> None:
    from benchmark import families
    from benchmark import run as bench_run
    from benchmark.harness import runtime
    from benchmark.runners import serve

    devices = runtime.require_tpu(1)
    runtime.enable_compile_cache()
    cell = bench_run.Cell.load(cell_name)
    compiles = runtime.CompileCounter()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "serve_variants.jsonl")
    with open(path, "w", encoding="utf-8") as out:
        for n in range(n_program):
            seed = 4_300_000_000 + 104729 * n
            t0 = time.perf_counter()
            ctx = bench_run.Context(cell=cell, seed=seed, seconds=seconds,
                                    trace=False, devices=devices,
                                    compiles=compiles)
            ran = serve.run(ctx)
            line = {"seed": seed, "program": ran["numbers"],
                    "metrics": ran["metrics"],
                    "completed": ran["notes"]["completed"],
                    "judged_tokens": ran["notes"]["judged_tokens"],
                    "memory_peak_bytes": ran["memory_peak_bytes"],
                    "run_s": time.perf_counter() - t0}
            if n < n_variant:
                # the variants judge the sample but for its longest request
                # (the first), which alone is half of a pass's time
                family = families.load(cell.config)
                rest = ran["samples"][1:]
                plain = family.judge(cell.config, seed, rest)
                line["program_rest"] = quantiles(plain["token_gaps"])
                for variant in variants:
                    t1 = time.perf_counter()
                    judged = family.judge(
                        cell.config, seed, rest, variant,
                        reference_logits=plain["reference_logits"])
                    line[variant] = dict(quantiles(judged["token_gaps"]),
                                         s=time.perf_counter() - t1)
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
         sys.argv[5:])
