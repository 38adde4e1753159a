"""Benchmark entry point: one function per paper table/figure + the roofline
report.  Prints ``name,us_per_call,derived`` CSV and writes a consolidated
``BENCH_summary.json`` (one gate-metric row per benchmark that ran).

  PYTHONPATH=src python -m benchmarks.run [--only fig3,fig5,...] [--smoke]
  PYTHONPATH=src python -m benchmarks.run --list

Summary rows are ``{benchmark, metric, value, direction, kind, threshold}``:
``direction`` says which way is better, ``kind`` separates machine-portable
``ratio`` metrics (speedups, overheads — what CI's regression check
compares across machines) from absolute ``time`` metrics, and ``threshold``
is the hard gate the standalone benchmark enforces on full runs (``null``
when the metric is informational or the run was ``--smoke``).
"""

import argparse
import json
import sys
from pathlib import Path

# key -> (module name, human description, passes smoke kwarg)
BENCHES = {
    "table2":    ("bench_complexity", "encode/decode op-count tables", False),
    "fig3":      ("bench_training_time", "MLP training wall-clock", False),
    "fig4":      ("bench_accuracy", "approximation error vs exact", False),
    "roundtrip": ("bench_roundtrip",
                  "fused vs loop coded rounds + encrypted overhead", True),
    "crypto":    ("bench_crypto", "MEA-ECC cipher throughput", True),
    "anytime":   ("bench_anytime", "anytime decoding error curves", True),
    "serve":     ("bench_serve", "deadline serving quality", True),
    "faults":    ("bench_faults",
                  "fault-injected rounds: defended vs undefended", True),
    "transport": ("bench_transport",
                  "socket mesh vs threads + live SIGKILL round", True),
    "adaptive":  ("bench_adaptive",
                  "adaptive redundancy vs every fixed wait policy", True),
    "roofline":  ("roofline", "kernel arithmetic-intensity report", False),
}
ALIASES = {"fig5": "table2", "fig6": "table2", "fig7": "table2"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: " + ",".join(
                        list(BENCHES) + sorted(ALIASES)))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few reps for benchmarks that "
                         "support it (CI); thresholds are not enforced")
    ap.add_argument("--list", action="store_true",
                    help="print available benchmark keys and exit")
    ap.add_argument("--summary-out",
                    default=str(Path(__file__).resolve().parent.parent
                                / "BENCH_summary.json"),
                    help="where to write the consolidated gate-metric rows")
    args = ap.parse_args()

    if args.list:
        for key, (mod, desc, smokeable) in BENCHES.items():
            extra = " (smoke-able)" if smokeable else ""
            print(f"{key:10s} {mod}: {desc}{extra}")
        for alias, key in sorted(ALIASES.items()):
            print(f"{alias:10s} -> {key}")
        return

    only = None
    if args.only:
        only = {ALIASES.get(k, k) for k in args.only.split(",")}
        unknown = only - set(BENCHES)
        if unknown:
            sys.exit(f"unknown benchmark(s): {','.join(sorted(unknown))} "
                     f"(see --list)")

    import importlib
    import inspect
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows, gates = [], []
    for key, (mod_name, _, smokeable) in BENCHES.items():
        if only is not None and key not in only:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        kw = {}
        if smokeable:
            # serve is always run at smoke scale from the aggregate driver
            kw["smoke"] = args.smoke or key == "serve"
        if "gates" in inspect.signature(mod.run).parameters:
            kw["gates"] = gates
        n_before = len(rows)
        mod.run(rows, **kw)
        if len(gates) == 0 or gates[-1]["benchmark"] != key:
            # headline fallback: first CSV row the module appended.  The
            # units column is not always a wall time (serve reports
            # tok/s), so no direction is claimed — informational only.
            if len(rows) > n_before:
                name, us, _ = rows[n_before]
                gates.append({"benchmark": key, "metric": name,
                              "value": round(us, 1), "direction": None,
                              "kind": "time", "threshold": None})

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    import jax
    summary = {"benchmark_summary": True, "smoke": args.smoke,
               "backend": jax.default_backend(), "rows": gates}
    Path(args.summary_out).write_text(json.dumps(summary, indent=2) + "\n")
    print(f"wrote {args.summary_out} ({len(gates)} gate rows)",
          file=sys.stderr)


if __name__ == '__main__':
    main()
