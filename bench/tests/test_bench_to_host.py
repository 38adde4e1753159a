"""The readers of the counters on the round product's copy to the host
(``to_host_chunked.round``, ``to_host_gbps.round``), on a trace of fused
rounds made on the CPU and on the trace recorded on the chip, whose copies
carry no such counters."""

import functools
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH
from test_bench_spans import RECORDED, _checkout
from yardstick import harness, spans, trace


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read


def _cpu_rounds_trace(root, shapes):
    """Fused rounds of the given (m, d, n) shapes traced on the CPU inside
    the harness's window, kept where a traced run keeps its trace."""
    import jax
    from repro.api import ClusterSpec, CodeSpec, PrivacySpec, Session
    spec = ClusterSpec(code=CodeSpec(scheme="spacdc", n_workers=10,
                                     k_blocks=4),
                       privacy=PrivacySpec(t_colluding=1), seed=5)
    ops = [(np.ones((m, d), np.float32), np.ones((d, n), np.float32))
           for m, d, n in shapes]
    out = root / ".bench_trace" / "fig3-round-device"
    with Session(spec) as s:
        for a, b in ops:                 # compile before the window
            s.matmul(a, b)
        jax.profiler.start_trace(str(out))
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                for i, (a, b) in enumerate(ops):
                    s.matmul(a, b, round_idx=i)
        finally:
            jax.profiler.stop_trace()
    return trace.find_xplane(out)


def test_copy_metrics_read_the_copy_spans_counters(tmp_path, monkeypatch):
    import jax
    from jax.profiler import ProfileData
    from repro.runtime import engine
    # row chunks here as on a TPU, for the one product of 4 KB or more
    monkeypatch.setattr(engine, "_CHUNK_PLATFORMS", (jax.default_backend(),))
    monkeypatch.setattr(engine, "_CHUNK_MIN_BYTES", 4096)
    shapes = [(48, 16, 8), (96, 16, 24), (5, 16, 7)]
    path = _cpu_rounds_trace(tmp_path, shapes)
    monkeypatch.setattr(spans, "for_measure",
                        functools.partial(spans.for_measure, root=tmp_path))
    copies = [e for p in ProfileData.from_file(str(path)).planes
              for line in p.lines for e in line.events
              if e.name == "spacdc.round.to_host"]
    stats = [dict(e.stats) for e in copies]
    assert [c["bytes"] for c in stats] == [4 * m * n for m, _, n in shapes]
    measure = {"kind": "round", "summary": SimpleNamespace(
        window_s=spans.reduce_file(path).window_s)}
    assert [c["chunked"] for c in stats] == [0, 1, 0]
    assert _reader("to_host_chunked.round")(measure) == pytest.approx(
        100.0 / 3)
    seconds = sum(e.duration_ns for e in copies) * 1e-9
    assert _reader("to_host_gbps.round")(measure) == pytest.approx(
        1e-9 * sum(4 * m * n for m, _, n in shapes) / seconds)
    serve = dict(measure, kind="serve")
    assert _reader("to_host_chunked.round")(serve) is None
    assert _reader("to_host_gbps.round")(serve) is None


def test_copy_metrics_read_nothing_where_copies_carry_no_counters(
        tmp_path, monkeypatch):
    root = _checkout(tmp_path, [("fig3-round-device", RECORDED)])
    monkeypatch.setattr(spans, "for_measure",
                        functools.partial(spans.for_measure, root=root))
    measure = {"kind": "round", "summary": trace.reduce_file(RECORDED)}
    assert _reader("to_host_ms.round")(measure) is not None   # found
    assert _reader("to_host_chunked.round")(measure) is None
    assert _reader("to_host_gbps.round")(measure) is None
