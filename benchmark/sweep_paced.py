#!/usr/bin/env python3
"""The paced sweep: one traced run of the paced cell at each of a few fixed
rates, and for each the readings that decide the knee — lag p50/p95 of the
window's first and second half, how late the generator ran, the largest
seal and the share of rows routed to the device.

    python3 benchmark/sweep_paced.py --rates 20,40,80 [--seconds 15] [--seed N]
        [--set key=value ...]

`--set` overrides a parameter of the mix: PERF.md's sweep was made over
500-row transactions alone, `--set bulk_every_transactions=0`.

The knee is the highest rate at which lag does not grow from the first half
to the second, the generator keeps its schedule, and the pipeline stays in
one batching regime. The cell's file then takes half of it (PERF.md)."""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORKLOAD = "pgbench-s10-null.insert-paced"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rates", required=True,
                    help="transactions per second, comma separated")
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--seed", type=int, default=2_500_000_001)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE", help="override a mix parameter")
    args = ap.parse_args()
    scratch = os.path.join(ROOT, ".bench_trace")
    os.makedirs(scratch, exist_ok=True)
    with open(os.path.join(HERE, "traffic", "insert-paced.json")) as f:
        base = json.load(f)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(base, transactions_per_second=rate,
                   **{k: json.loads(v) for k, v in
                      (kv.split("=", 1) for kv in args.set)})
        path = os.path.join(ROOT, f".bench_sweep_{rate:g}.json")
        with open(path, "w") as f:
            json.dump(mix, f)
        try:
            run = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 WORKLOAD, "--seed", str(args.seed + i), "--seconds",
                 str(args.seconds), "--trace", "1", "--traffic-file", path],
                capture_output=True, text=True)
        finally:
            os.remove(path)
        row = {"transactions_per_s": rate, "rc": run.returncode}
        if run.returncode == 0:
            line = json.loads(run.stdout.strip().splitlines()[-1])
            notes = next(json.loads(ln) for ln in run.stderr.splitlines()
                         if ln.startswith('{"setup"'))
            row.update(correct=line["correct"], failed=line["failed"],
                       attempted=line["attempted"],
                       halves=notes["extra"]["halves"],
                       **{k: v["value"] for k, v in line["metrics"].items()})
        else:
            row["stderr"] = run.stderr[-600:]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
