"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json`` (reference beside it, ``<config>.py``), its
traffic mix in ``bench/traffic/<traffic>.json``, which names the driver
``bench/drivers/<driver>.py`` that runs it, and each per-layer metric's
reader in ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number that
decided ``correct`` beside its limit, also printed as the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits 3.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NO_CHIP = 3


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    if a.seed < 0 or a.seconds <= 0:
        p.error("--seed must be >= 0 and --seconds > 0")
    return a


def cell_metrics(bench: dict, cell: str):
    """(end-to-end entries, per-layer entries) that the cell reports."""
    def applies(entry, reported=None):
        if "workloads" in entry:
            return cell in entry["workloads"]
        return reported is None or entry["moves"] in reported
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    return e2e, [m for m in bench["per_layer"] if applies(m, names)]


def _compile_cache():
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at the fixed path ``<checkout>/.jax_cache``.  Every program is
    kept, however fast it compiled, so a warm set-up compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    args = _args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return NO_CHIP
    _compile_cache()

    from yardstick import harness
    ctx = harness.Context(workload=args.workload, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          root=ROOT, t_start=T_START)
    driver = harness.load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    ctx.mark("chip_found")
    gc.collect()
    out = driver.run(config, traffic, ctx)

    e2e, per_layer = cell_metrics(bench, args.workload)
    metrics = {}
    if args.trace:
        for m in per_layer:
            reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(out.measure)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": all(c.ok for c in out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if args.trace:
        summary = out.measure["summary"]
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["breakdown"] = out.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    print("setup " + " ".join(f"{k}={v:.3f}" for k, v in ctx.marks.items()),
          file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name} value={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
