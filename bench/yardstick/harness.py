"""What every driver shares: the run's context, the count of compilations
inside the measured window, the profiler around a traced stretch, device
memory, and the checks that decide ``correct``."""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time
from pathlib import Path
from typing import List, Optional

# lowering runs once for every program JAX has not seen in this process,
# whether XLA then compiles it or finds it in the persistent cache
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``: it passes at or under its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # NaN never passes, and neither does a number with no limit set
        return self.limit is not None and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    attempted: int
    failed: int
    end_to_end: dict                 # metric name -> value
    checks: List[Check]
    memory_peak_bytes: int
    measure: Optional[dict] = None   # what per-layer readers read (trace 1)
    breakdown: Optional[dict] = None


@dataclasses.dataclass
class Context:
    """One run: its seed, window length, whether it traces, and where."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    root: Path                       # the checkout
    t_start: float                   # perf_counter at process start
    marks: dict = dataclasses.field(default_factory=dict)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def mark(self, name: str) -> None:
        """Note how far into the run a stage of set-up ended."""
        self.marks[name] = self.since_start()

    @property
    def trace_dir(self) -> Path:
        return self.root / ".bench_trace" / self.workload


class CompileWatch:
    """Counts lowerings while open: a program JAX had not seen before."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        return False


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


@contextlib.contextmanager
def traced(ctx: Context, result: dict):
    """Profile the block as the ``bench.window`` span; on exit reduce the
    trace into ``result["summary"]``.  The trace stays in the checkout's
    ``.bench_trace/<workload>`` until the next traced run of that cell."""
    import jax
    from . import trace
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    ctx.trace_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(ctx.trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()
    result["summary"] = trace.reduce_file(trace.find_xplane(ctx.trace_dir))


def span(name: str):
    """A host span of the harness's own, named ``bench.<name>``."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def seed_key(seed: int, salt: int = 0):
    """A JAX PRNG key for any whole-number seed, also one past 32 bits:
    the low 32 bits seed the key, the rest are folded in."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, salt)


def cluster_spec(config: dict, traffic: dict, seed: int):
    """The ``ClusterSpec`` of a cell: the configuration's deployment, with
    the traffic mix's session settings laid over it, and the cluster seed
    (straggler draws, privacy noise) taken from the run's seed."""
    from repro.api import ClusterSpec

    def merge(base: dict, over: dict) -> dict:
        out = dict(base)
        for k, v in over.items():
            out[k] = merge(out[k], v) if isinstance(v, dict) and \
                isinstance(out.get(k), dict) else v
        return out

    d = merge(config["cluster"], traffic.get("cluster", {}))
    d["seed"] = seed % 2 ** 31
    return ClusterSpec.from_dict(d)


def load_module(path: Path):
    """Import a benchmark file by its path (names of cells, metrics and
    configurations are not Python identifiers)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_dir() -> Path:
    return Path(__file__).resolve().parents[1]
