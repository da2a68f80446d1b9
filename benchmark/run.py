#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: set-up (weights from the seed, every shape warmed up) ->
measure for ``--seconds`` -> free the program -> the plain reference and the
comparison -> one last JSON line on standard output.  Without a TPU it fails
and prints no result.  Everything about a cell is data: ``BENCHMARK.json``
names the cell, its configuration file and its metrics;
``benchmark/workloads/<cell>.json`` holds the traffic mix and the limits of
the comparison; ``benchmark/layer_metrics/<metric>.json`` names the reader
of a per-layer metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up counts from here

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Optional         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HERE = os.path.join(ROOT, "benchmark")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Cell":
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json (it has "
                f"{[w['name'] for w in bench['workloads']]})")
        config_entry = next(c for c in bench["configs"]
                            if c["name"] == entry["config"])

        def mine(metric: dict) -> bool:
            return name in metric.get("workloads", [name])

        return cls(
            name=name, chips=entry["chips"], config_name=entry["config"],
            config=load_json(os.path.join(root, config_entry["file"])),
            mix=load_json(os.path.join(root, "benchmark", "workloads",
                                       name + ".json")),
            end_to_end=[m for m in bench["end_to_end"] if mine(m)],
            per_layer=[m for m in bench["per_layer"] if mine(m)])


@dataclasses.dataclass
class Context:
    """What a runner gets."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    compiles: object
    setup_s: Optional[float] = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def mix(self) -> dict:
        return self.cell.mix

    def window_opens(self) -> None:
        """Called by the runner at the instant set-up ends."""
        self.setup_s = time.perf_counter() - T_PROCESS


def read_layer_metrics(cell: Cell, reading: dict, root: str = ROOT) -> dict:
    """Every per-layer metric of the cell through its own reader.  A reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for metric in cell.per_layer:
        spec = load_json(os.path.join(root, "benchmark", "layer_metrics",
                                      metric["name"] + ".json"))
        path = os.path.join(root, "benchmark", "layer_metrics", "readers",
                            spec["reader"] + ".py")
        module_spec = importlib.util.spec_from_file_location(
            "benchmark_reader_" + spec["reader"], path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        value = module.read(reading, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def finite(x):
    """JSON has no infinity: a missed tail reads as 1e12."""
    return x if x == x and abs(x) != float("inf") else 1e12


def result_line(cell: Cell, ctx: Context, ran: dict,
                root: str = ROOT) -> dict:
    """The one JSON object the driver reads."""
    from benchmark.harness import compare, peaks, runtime, trace_reduce

    limits = cell.mix["limits"]
    correct, checks = compare.verdict(ran["numbers"], limits)
    device = runtime.device_info(ctx.devices)
    device["memory_peak_bytes"] = ran["memory_peak_bytes"]
    line = {"correct": correct, "attempted": ran["attempted"],
            "failed": ran["failed"]}
    if ctx.trace:
        reading = dict(ran["reading"])
        reading["peak"] = peaks.peak_for(device["kind"])
        reading["chips"] = cell.chips
        line["metrics"] = read_layer_metrics(cell, reading, root)
        trace = reading.get("trace")
        if trace is not None:
            device["busy_s"] = trace_reduce.busy_seconds(trace)
            device["window_s"] = reading["window_s"]
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(trace, 10),
                "idle_gaps": trace_reduce.idle_gaps(trace, 10)}
    else:
        values = dict(ran["metrics"], setup_s=ctx.setup_s)
        line["metrics"] = {
            m["name"]: {"value": finite(values[m["name"]]),
                        "unit": m["unit"]}
            for m in cell.end_to_end}
    line["device"] = device
    line["notes"] = dict(ran.get("notes", {}), read_not_held={
        k: v for k, v in ran["numbers"].items() if k not in limits})
    # each number compared beside its limit, last in the line
    line["checks"] = {k: {"value": finite(c["value"]), "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--look", metavar="FILE", default=None,
                    help="with --trace 1: also write the trace's planes, "
                         "lines and commonest names there, and a 30 ms "
                         "slice of its events, for a look by hand")
    args = ap.parse_args(argv)

    cell = Cell.load(args.workload, root)
    from benchmark.harness import runtime

    try:
        devices = runtime.require_tpu(cell.chips)
    except runtime.NoAccelerator as e:
        print(f"benchmark: {e}. It does not run without the chip.",
              file=sys.stderr, flush=True)
        return 1
    where = runtime.enable_compile_cache()
    print(f"benchmark: {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache at {where}",
          file=sys.stderr, flush=True)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devices=devices,
                  compiles=runtime.CompileCounter())
    runner = importlib.import_module(
        "benchmark.runners." + cell.config["runner"])
    ran = runner.run(ctx)
    line = result_line(cell, ctx, ran, root)
    if args.look and ran["reading"].get("trace") is not None:
        from benchmark.harness import trace_reduce

        os.makedirs(os.path.dirname(os.path.abspath(args.look)),
                    exist_ok=True)
        trace = ran["reading"]["trace"]
        with open(args.look, "w", encoding="utf-8") as f:
            json.dump({"summary": trace_reduce.summary(trace),
                       "slice": trace_reduce.time_slice(trace, 30_000_000)},
                      f)
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr, flush=True)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
