"""Readings that the limits of ``correct`` are set from: for each seed, the
compared number of the program's own run and of the control, the
configuration's reference one precision lower put in the program's place.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

Runs on the chip at the cell's own size, every seed in this one process,
each with a window of ``--seconds`` (long enough to finish the mix's
longest requests).  Prints one JSON line per seed, then, for each number,
the largest program reading, the smallest control reading and their ratio.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    a = p.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("the control runs on the chip", file=sys.stderr)
        return 3
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from yardstick import harness
    driver = harness.load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    found = {}
    for seed in a.seeds:
        r = driver.control_readings(config, traffic, seed, a.seconds)
        print(json.dumps({"seed": seed, **{k: {"program": v[0], "control": v[1]}
                                           for k, v in r.items()}}), flush=True)
        for k, v in r.items():
            found.setdefault(k, []).append(v)
    for k, v in found.items():
        lower = max(x[0] for x in v)
        upper = min(x[1] for x in v)
        print(json.dumps({"number": k, "lower": lower, "upper": upper,
                          "ratio": upper / lower if lower else None}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
