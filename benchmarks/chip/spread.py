"""Run a cell several times and report each metric's spread.

    python3 benchmarks/chip/spread.py --workload <name> --seeds <n,n,...> \\
        --out <dir> [--sets 2] [--seconds 10] [--trace 0]

Each run is its own ``run.py`` process, one after another (this parent
never touches JAX, so each child has the chip to itself). With ``--sets
2`` the same seeds run twice, set after set. For every metric and set it
prints the median and the spread, the distance between the first and
third quartiles (``statistics.quantiles(values, n=4)``) as a share of the
median; the bound of an end-to-end metric is set from the wider set.
Every run's result line goes to ``<out>/<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True,
                    help="directory for <workload>.jsonl")
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    with open(out / f"{args.workload}.jsonl", "a") as log:
        for k in range(args.sets):
            for seed in seeds:
                t0 = time.monotonic()
                p = subprocess.run(
                    [sys.executable, str(HERE / "run.py"), "--workload",
                     args.workload, "--seed", str(seed), "--seconds",
                     str(args.seconds), "--trace", str(args.trace)],
                    capture_output=True, text=True, timeout=1500)
                wall = time.monotonic() - t0
                try:
                    res = json.loads(p.stdout.strip().splitlines()[-1])
                except (IndexError, ValueError):
                    res = None
                row = {"set": k, "seed": seed, "rc": p.returncode,
                       "wall_s": wall, "result": res}
                if res is None:
                    row["stderr_tail"] = p.stderr[-2000:]
                log.write(json.dumps(row) + "\n")
                log.flush()
                rows.append(row)
                metrics = res["metrics"] if res else {}
                print(f"set {k} seed {seed} rc {p.returncode} wall "
                      f"{wall:.1f} s correct "
                      f"{res['correct'] if res else None} " + " ".join(
                          f"{n} {m['value']!r}" for n, m in metrics.items()),
                      flush=True)
    names = sorted({n for r in rows if r["result"]
                    for n in r["result"]["metrics"]})
    for n in names:
        for k in range(args.sets):
            vals = [r["result"]["metrics"][n]["value"] for r in rows
                    if r["set"] == k and r["result"]
                    and n in r["result"]["metrics"]]
            if vals:
                print(f"{n} set {k}: n {len(vals)} median "
                      f"{statistics.median(vals)!r} spread "
                      f"{spread(vals)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
