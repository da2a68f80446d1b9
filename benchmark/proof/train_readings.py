#!/usr/bin/env python3
"""Readings for the training cell's limits, in one process on the chip.

For each seed: the program's first steps against the reference (the lower
reading), then in the reference's own place the control (float8 matmul
inputs) and the fault "half of the batch left out" (the upper readings).
One JSON line per seed on standard output and in ``chiprun_out/``.

    python benchmark/proof/train_readings.py <cell> <seeds for the program> <seeds for control and fault>
"""

import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(cell_name: str, n_program: int, n_upper: int) -> None:
    import jax

    from benchmark import run as bench_run
    from benchmark.harness import compare, runtime, traffic
    from benchmark.runners import train

    runtime.require_tpu(1)
    runtime.enable_compile_cache()
    cell = bench_run.Cell.load(cell_name)
    cfg, mix = cell.config, cell.mix
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "train_readings.jsonl"), "w")
    grad_step = None
    for n in range(n_program):
        seed = 4_100_000_000 + 7919 * n
        t0 = time.perf_counter()
        batches = traffic.train_batches(mix, cfg["held_vocab"],
                                        cfg["type_vocab_size"], seed,
                                        train.FOLLOWED)
        prog = train.Program(cfg, mix, seed, grad_step)
        grad_step = prog.grad_step
        seen = train.first_steps(prog, batches, mix["betas"][0])
        del prog
        gc.collect()
        t1 = time.perf_counter()
        ref = train.follow_reference(cfg, mix, seed, batches)
        t2 = time.perf_counter()
        line = {"seed": seed, "program": compare.train_numbers(seen, ref),
                "program_s": t1 - t0, "reference_s": t2 - t1,
                "losses": ref["losses"],
                "leaves": {"reference": [ref["grad_norms"],
                                         ref["change_norms"]],
                           "program": [seen["grad_norms"],
                                       seen["change_norms"]]}}
        if n < n_upper:
            control = train.follow_reference(cfg, mix, seed, batches, "fp8")
            line["control_fp8"] = compare.train_numbers(control, ref)
            line["leaves"]["control_fp8"] = [control["grad_norms"],
                                             control["change_norms"]]
            half = [{k: v[:v.shape[0] // 2] for k, v in b.items()}
                    for b in batches]
            halved = train.follow_reference(cfg, mix, seed, half)
            line["fault_half_batch"] = compare.train_numbers(halved, ref)
            line["leaves"]["fault_half_batch"] = [halved["grad_norms"],
                                                  halved["change_norms"]]
            line["upper_s"] = time.perf_counter() - t2
        print(json.dumps({k: v for k, v in line.items() if k != "leaves"}),
              flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
    out.close()


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
