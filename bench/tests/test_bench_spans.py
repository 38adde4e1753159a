"""The reduction of the program's spans pinned on a hand-written trace and
on a trace recorded on the chip, and the search for the traced run's own
trace file."""

import os
import shutil

import pytest

from conftest import BENCH
from yardstick import spans, trace

US = 1_000_000      # picoseconds in a microsecond

# stat metadata ids of the counters below
STATS = {"bucket": 1, "live": 2, "new_bucket": 3, "fn": 4}


def _stat(key, value):
    kind = "str_value" if isinstance(value, str) else "int64_value"
    v = f'"{value}"' if isinstance(value, str) else value
    return f"stats {{ metadata_id: {STATS[key]} {kind}: {v} }} "


def _events(*evs):
    out = ""
    for m, s, d, *stats in evs:
        st = "".join(_stat(k, v) for k, v in (stats[0] if stats else {})
                     .items())
        out += (f"events {{ metadata_id: {m} offset_ps: {s * US} "
                f"duration_ps: {d * US} {st}}}\n")
    return out


def _meta(*names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))


def _stat_meta():
    return "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in STATS.items())


# times in microseconds from the line's start.  Two loop steps: the first
# with every inner span and a trace inside its dispatch, the second with
# only its copy to the host; a host event that is no program span
# (np.asarray) inside a copy; one step after the window, which is not
# counted
SYNTHETIC = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events((1, 0, 100), (2, 5, 55, {"bucket": 8, "live": 7}),
         (3, 5, 5), (4, 10, 10, {"new_bucket": 1}), (5, 12, 6, {"fn": "f"}),
         (6, 20, 20), (7, 40, 18), (8, 41, 16),
         (2, 62, 28, {"bucket": 4, "live": 3}), (7, 70, 18),
         (2, 120, 10, {"bucket": 4, "live": 2}))}  }}
{_meta("bench.window", "spacdc.serve.step", "spacdc.serve.plan",
       "spacdc.serve.dispatch", "spacdc.trace", "spacdc.serve.wait",
       "spacdc.serve.to_host", "np.asarray")}{_stat_meta()}}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{_events((1, 15, 20), (2, 45, 5), (3, 95, 15))}  }}
{_meta("fusion.1", "copy.1", "fusion.2")}}}
planes {{ id: 3 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 }} }}
"""


def _synthetic():
    from jax.profiler import ProfileData
    return spans.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_counts_totals_self_times_and_counters():
    s = _synthetic()
    assert s.window_s == pytest.approx(100e-6)
    assert s.count == {"spacdc.serve.step": 2, "spacdc.serve.plan": 1,
                       "spacdc.serve.dispatch": 1, "spacdc.trace": 1,
                       "spacdc.serve.wait": 1, "spacdc.serve.to_host": 2}
    assert s.total_s["spacdc.serve.step"] == pytest.approx(83e-6)
    assert s.total_s["spacdc.serve.to_host"] == pytest.approx(36e-6)
    # a step less its plan, dispatch, wait and copies; a dispatch less
    # its trace; a copy is not shortened by a host event of another kind
    assert s.self_s["spacdc.serve.step"] == pytest.approx(12e-6)
    assert s.self_s["spacdc.serve.dispatch"] == pytest.approx(4e-6)
    assert s.self_s["spacdc.serve.to_host"] == pytest.approx(36e-6)
    assert s.counters["spacdc.serve.step"] == {"bucket": 12, "live": 10}
    assert s.counters["spacdc.serve.dispatch"] == {"new_bucket": 1}
    assert s.counters["spacdc.trace"] == {}                # fn: a string
    assert s.ms_per("spacdc.serve.to_host", "spacdc.serve.step") == \
        pytest.approx(0.018)
    assert s.ms_per("spacdc.serve.to_host", "spacdc.round") is None


def test_idle_split_by_overlap_over_the_innermost_span():
    from jax.profiler import ProfileData
    s = _synthetic()
    # holes [0, 15], [35, 45] and [50, 95]; [35, 45] straddles the wait
    # and the first copy, [0, 15] starts under no span and ends inside
    # the trace nested in the dispatch
    assert s.idle_s == pytest.approx({
        "spacdc.serve.plan": 5e-6, "spacdc.serve.dispatch": 2e-6,
        "spacdc.trace": 3e-6, "spacdc.serve.wait": 5e-6,
        "spacdc.serve.to_host": 31e-6, "spacdc.serve.step": 12e-6})
    assert s.idle_unspanned_s == pytest.approx(12e-6)
    t = trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert sum(s.idle_s.values()) + s.idle_unspanned_s == pytest.approx(
        t.window_s - t.busy_s)


def test_a_trace_without_program_spans_reads_empty():
    from jax.profiler import ProfileData
    s = spans.reduce_profile(ProfileData.from_text_proto(
        SYNTHETIC.replace('"spacdc.', '"other.')))
    assert s.count == {} and s.idle_s == {}
    assert s.idle_unspanned_s == pytest.approx(70e-6)
    assert s.ms_per("spacdc.serve.to_host", "spacdc.serve.step") is None


RECORDED = BENCH / "testdata" / "fig3-round-3-spans.xplane.pb"
OLDER = BENCH / "testdata" / "fig3-round-3.xplane.pb"


def test_reduction_of_a_recorded_chip_trace():
    # three warm fig3 plain rounds traced on a TPU v5 lite under the
    # harness's window; the values below are this reduction's, pinned
    t = trace.reduce_file(RECORDED)
    s = spans.reduce_file(RECORDED)
    assert s.window_s == t.window_s == pytest.approx(0.07429465, rel=1e-9)
    assert s.count["spacdc.round"] == t.module_runs["jit__round"] == 3
    for name in ("plan", "dispatch", "wait", "to_host"):
        assert s.count[f"spacdc.round.{name}"] == 3
    assert "spacdc.trace" not in s.count            # warm: nothing traced
    assert all(c == {} for c in s.counters.values())  # rounds carry none
    assert s.ms_per("spacdc.round.to_host", "spacdc.round") == \
        pytest.approx(12.360030333, rel=1e-9)
    assert s.ms_per("spacdc.round.plan", "spacdc.round") == \
        pytest.approx(0.37572667, rel=1e-7)
    assert s.self_s["spacdc.round"] == pytest.approx(0.00118597, rel=1e-9)
    # every idle nanosecond is attributed; the copy to the host holds the
    # most, and under 0.4% lies under no program span
    assert sum(s.idle_s.values()) + s.idle_unspanned_s == pytest.approx(
        t.window_s - t.busy_s, rel=1e-9)
    assert s.idle_s["spacdc.round.to_host"] == pytest.approx(0.037080091,
                                                             rel=1e-9)
    assert s.idle_s["spacdc.round.wait"] == pytest.approx(0.005990156,
                                                          rel=1e-9)
    assert s.idle_unspanned_s == pytest.approx(0.0001697, rel=1e-6)


def _checkout(tmp_path, files):
    """A checkout whose ``.bench_trace`` holds ``files`` (cell, source),
    each written one second after the one before."""
    for i, (cell, src) in enumerate(files):
        d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        dst = d / f"{i}.xplane.pb"
        shutil.copy(src, dst)
        os.utime(dst, ns=(10 ** 18 + i * 10 ** 9,) * 2)
    return tmp_path


def test_the_traced_run_finds_its_own_trace(tmp_path):
    measure = {"kind": "round", "summary": trace.reduce_file(RECORDED)}
    root = _checkout(tmp_path, [("other-cell", OLDER),
                                ("fig3-round-device", RECORDED)])
    s = spans.for_measure(measure, root=root)
    assert s is not None and s.count["spacdc.round"] == 3
    # a window a nanosecond off is another run's
    off = trace.reduce_file(RECORDED)
    off.window_s += 1e-9
    assert spans.for_measure({"kind": "round", "summary": off},
                             root=root) is None
    # the newest trace is another run's: nothing is read
    newer = _checkout(tmp_path / "b", [("fig3-round-device", RECORDED),
                                       ("other-cell", OLDER)])
    assert spans.for_measure(measure, root=newer) is None
    assert spans.for_measure(measure, root=tmp_path / "none") is None
    assert spans.for_measure(None, root=root) is None
