#!/usr/bin/env python3
"""Readings for a serving cell's limit, in one process on the chip.

For each seed: one short run of the cell at its own load (the runner
itself), whose sampled requests the reference judges (the lower reading);
for the first seeds also the control in the reference's place: at each
position of the same prompts and tokens, the gap of the token that float8
matmul inputs put first (the upper reading).

    python benchmark/proof/serve_readings.py <cell> <seconds> <seeds> <control seeds>
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(cell_name: str, seconds: float, n_program: int,
         n_control: int) -> None:
    from benchmark import families
    from benchmark import run as bench_run
    from benchmark.harness import runtime
    from benchmark.runners import serve

    devices = runtime.require_tpu(1)
    runtime.enable_compile_cache()
    cell = bench_run.Cell.load(cell_name)
    compiles = runtime.CompileCounter()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "serve_readings.jsonl"), "w")
    for n in range(n_program):
        seed = 4_200_000_000 + 7919 * n
        t0 = time.perf_counter()
        ctx = bench_run.Context(cell=cell, seed=seed, seconds=seconds,
                                trace=False, devices=devices,
                                compiles=compiles)
        ran = serve.run(ctx)
        line = {"seed": seed, "program": ran["numbers"],
                "metrics": ran["metrics"], "notes": ran["notes"],
                "run_s": time.perf_counter() - t0}
        if n < n_control:
            t1 = time.perf_counter()
            line["control_fp8"] = families.load(cell.config).judge(
                cell.config, seed, ran["samples"], "fp8")
            line["control_s"] = time.perf_counter() - t1
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
    out.close()


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
