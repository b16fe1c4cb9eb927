"""Runs of one cell, one after another, each a process of its own; the parent
stays off jax. For the two sets of 6 that a bound is set from, and for the
traced runs.

    python3 benchmarks/tools/sets.py <cell> --seeds 1,2,3,4,5,6 --sets 2 --seconds 30 --trace 0

Each run's result line is appended to ``chiprun_out/runs_<cell>.jsonl`` with
its seed, set, wall time and the end of its standard error. At the end, for
each metric and each set: the median and the spread (the distance between the
first and the third quartile by ``statistics.quantiles(values, n=4)``, as a
share of the median).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"runs_{args.cell}.jsonl")
    by_set = {}
    for k in range(args.sets):
        for seed in seeds:
            cmd = command + ["--workload", args.cell, "--seed", str(seed),
                             "--seconds", str(args.seconds), "--trace",
                             str(args.trace)]
            t0 = time.time()
            p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
            wall = time.time() - t0
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() \
                else ""
            try:
                line = json.loads(last)
            except ValueError:
                line = None
            rec = {"cell": args.cell, "set": k, "seed": seed,
                   "trace": args.trace, "seconds": args.seconds,
                   "rc": p.returncode, "wall_s": round(wall, 1),
                   "line": line, "stderr_end": p.stderr[-1500:]}
            with open(out_path, "a") as f:
                f.write(json.dumps(rec) + "\n")
            if line is None:
                print(f"set {k} seed {seed}: rc={p.returncode} NO RESULT\n"
                      + p.stderr[-2000:], flush=True)
                continue
            vals = {n: m["value"] for n, m in line["metrics"].items()}
            print(f"set {k} seed {seed}: correct={line['correct']} "
                  f"wall={wall:.0f}s attempted={line['attempted']} " +
                  " ".join(f"{n}={v:.6g}" for n, v in vals.items()) +
                  " | " + " ".join(
                      f"{c['name']}={c['value']:.3g}"
                      for c in line["compared"]), flush=True)
            for n, v in vals.items():
                by_set.setdefault(n, {}).setdefault(k, []).append(v)
    for n, sets in by_set.items():
        for k, vals in sets.items():
            sp = spread(vals)
            print(f"SPREAD {args.cell} {n} set {k}: n={len(vals)} median="
                  f"{statistics.median(vals):.6g} spread="
                  f"{'n/a' if sp is None else format(sp, '.5f')} "
                  f"min={min(vals):.6g} max={max(vals):.6g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
