"""Readings that set the limits of `correct` (PERF.md, "How correct is decided").

    python3 -m benchmark.control --workload <name> --seeds 11,12,13 [--seconds 1]
        [--set analyzer.precision.score.dtype=bfloat16]

For each seed, in one process: build the cell's service, serve its plans
for --seconds through the same path a benchmark run drives, and print one
JSON line with two readings of every compared number: `sound`, the
program's answer against the float64 reference, and `control`, the same
reference computed in bfloat16 put in the program's place.  --set changes
a service key of the configuration: with the program's own bfloat16
scoring switched on (`analyzer.precision.score.dtype=bfloat16`), `sound`
holds the readings of that control, which `objective_gap` has to fail.
Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def readings(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    import ml_dtypes

    from benchmark import correctness, run

    result, dep = run.run_cell(config, traffic, seed, seconds, False, log=lambda m: None)
    served = run.checked_plans(result, seed, traffic)
    control = correctness.Reference(dep, config, dtype=ml_dtypes.bfloat16)
    sound = run.compare_plans(served, dep, config)
    lowp = run.compare_plans(served, dep, config, control=control)
    return {
        "seed": seed,
        "plans": len(result.done),
        "sound": correctness.worst(sound) if sound else None,
        "control": correctness.worst(lowp) if lowp else None,
        "balancedness": [r["_balancedness"] for r in sound],
    }


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    _bench, cell, config, traffic = run.resolve(args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        config["service"][k] = v
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    run.require_chip(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(config, traffic, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
