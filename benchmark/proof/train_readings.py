#!/usr/bin/env python3
"""Readings for a training cell's limits, in one process on the cell's chips.

For each seed: the program's first steps against the reference (the lower
reading), then in the reference's own place the control (float8 matmul
inputs) and the fault "half of the batch left out", and on a cell of several
chips the fault "the exchange between chips left out" planted in the program
(the upper readings).  One JSON line per seed on standard output and in
``chiprun_out/``.

    python benchmark/proof/train_readings.py <cell> <seeds for the program> <seeds for control and faults> [first seed]
"""

import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(cell_name: str, n_program: int, n_upper: int,
         first_seed: int = 4_100_000_000) -> None:
    from apex_tpu.parallel import DistributedDataParallel
    from benchmark import families
    from benchmark import run as bench_run
    from benchmark.harness import runtime
    from benchmark.runners import train

    cell = bench_run.Cell.load(cell_name)
    devices = runtime.require_tpu(cell.chips)
    runtime.enable_compile_cache()
    cfg, mix = cell.config, cell.mix
    family = families.load(cfg)
    beta1 = mix["betas"][0]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            cell_name + ".readings.jsonl"), "w")
    grad_step, alone_step = None, None
    exchange = DistributedDataParallel.allreduce_gradients
    for n in range(n_program):
        seed = first_seed + 7919 * n
        t0 = time.perf_counter()
        batches = family.batches(cfg, mix, seed, train.FOLLOWED)
        prog = train.Program(cfg, mix, seed, devices, grad_step)
        grad_step = prog.grad_step
        seen = train.first_steps(prog, batches, beta1)
        del prog
        gc.collect()
        alone = None
        if n < n_upper and cell.chips > 1:
            # every chip steps on its own gradient
            DistributedDataParallel.allreduce_gradients = \
                lambda self, grads: grads
            try:
                prog = train.Program(cfg, mix, seed, devices, alone_step)
                alone_step = prog.grad_step
                alone = train.first_steps(prog, batches, beta1)
            finally:
                DistributedDataParallel.allreduce_gradients = exchange
            del prog
            gc.collect()
        t1 = time.perf_counter()
        ref = family.follow(cfg, mix, seed, batches)
        t2 = time.perf_counter()
        line = {"seed": seed, "program": train.numbers_of(seen, ref),
                "program_s": t1 - t0, "reference_s": t2 - t1,
                "losses": ref["losses"],
                "leaves": {"reference": [ref["grad_norms"],
                                         ref["change_norms"]],
                           "program": [seen["grad_norms"],
                                       seen["change_norms"]]}}
        if alone is not None:
            line["fault_no_exchange"] = train.numbers_of(alone, ref)
        if n < n_upper:
            control = family.follow(cfg, mix, seed, batches, "fp8")
            line["control_fp8"] = train.numbers_of(control, ref)
            line["leaves"]["control_fp8"] = [control["grad_norms"],
                                             control["change_norms"]]
            half = [{k: v[:v.shape[0] // 2] for k, v in b.items()}
                    for b in batches]
            halved = family.follow(cfg, mix, seed, half)
            line["fault_half_batch"] = train.numbers_of(halved, ref)
            line["leaves"]["fault_half_batch"] = [halved["grad_norms"],
                                                  halved["change_norms"]]
            line["upper_s"] = time.perf_counter() - t2
        print(json.dumps({k: v for k, v in line.items() if k != "leaves"}),
              flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
    out.close()


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
         *(int(a) for a in sys.argv[4:5]))
