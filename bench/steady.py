"""Steadiness of one cell: run it k times, one process after another, and
report each metric's median and spread, and the bounds that spread admits.

    python3 bench/steady.py --workload <cell> --seconds <s> --seeds 11 12 13 [--sets 2] [--trace 0] [--out DIR]

Each set runs the given seeds in order; with ``--sets 2`` the second set
repeats the same seeds.  For each metric and set it prints the median and
two spreads, both as shares of the median:

* ``iqr``: the distance between the first and third quartile, as
  ``statistics.quantiles(values, n=4)`` gives them;
* ``trim``: the range of the runs after leaving out the run farthest from
  the median, where that narrows it.

A bound is too tight where the mean of the sets' ``trim`` spreads is more
than half of it, and too loose where it is over eight times the widest
``iqr`` of all the runs; ``admits`` gives that interval, and ``pick`` five
times the widest spread, never under 1%.  Every run's result line is kept
in ``<DIR>/<cell>.jsonl`` (default ``.bench_steady`` in the checkout).
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def iqr_spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values) -> float:
    med = statistics.median(values)
    full = max(values) - min(values)
    if len(values) > 2:
        far = max(range(len(values)), key=lambda i: abs(values[i] - med))
        rest = values[:far] + values[far + 1:]
        full = min(full, max(rest) - min(rest))
    return full / med


def run_once(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError(f"{workload} seed {seed}: exit {proc.returncode}")
    out = json.loads(lines[-1])
    out["seed"] = seed
    return out


def summarize(sets) -> dict:
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    report = {}
    for name in names:
        per_set = [[r["metrics"][name]["value"] for r in s] for s in sets]
        every = [v for s in per_set for v in s]
        row = {"medians": [statistics.median(s) for s in per_set],
               "iqr": [iqr_spread(s) for s in per_set if len(s) > 1],
               "trim": [trimmed_spread(s) for s in per_set if len(s) > 1]}
        if len(every) > 1 and row["trim"]:
            widest = max(iqr_spread(every), *row["trim"], *row["iqr"])
            row["admits"] = [2 * statistics.mean(row["trim"]),
                             8 * iqr_spread(every)]
            row["pick"] = max(0.01, 5 * widest)
        report[name] = row
    return report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", type=Path, default=ROOT / ".bench_steady")
    a = p.parse_args(argv)
    out_dir = a.out
    out_dir.mkdir(parents=True, exist_ok=True)
    sets = []
    with open(out_dir / f"{a.workload}.jsonl", "a") as log:
        for k in range(a.sets):
            runs = []
            for seed in a.seeds:
                r = run_once(a.workload, seed, a.seconds, a.trace)
                r["set"] = k
                log.write(json.dumps(r) + "\n")
                log.flush()
                print(json.dumps({"set": k, "seed": seed,
                                  "correct": r["correct"],
                                  "metrics": {m: v["value"] for m, v in
                                              r["metrics"].items()},
                                  "checks": r["checks"]}), flush=True)
                runs.append(r)
            sets.append(runs)
    print(json.dumps({"workload": a.workload, "seconds": a.seconds,
                      "summary": summarize(sets)}, indent=1), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
