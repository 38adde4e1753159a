"""The trace reduction pinned on a hand-written trace and on a trace
recorded on the chip."""

import pytest

from conftest import BENCH
from yardstick import trace

US = 1_000_000      # picoseconds in a microsecond


def _events(*evs):
    return "".join(f"events {{ metadata_id: {m} offset_ps: {s * US} "
                   f"duration_ps: {d * US} }}\n" for m, s, d in evs)


def _meta(*names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))


# times in microseconds from the line's start
SYNTHETIC = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{_events((1, 0, 100), (2, 0, 55), (3, 40, 18), (4, 70, 25))}  }}
{_meta("bench.window", "bench.round", "np.asarray", "plan_round")}}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
{_events((1, 5, 40), (2, 60, 10))}  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{_events((3, 10, 20), (4, 25, 15), (5, 60, 10), (6, 95, 15))}  }}
{_meta("jit__round(7)", "jit_step(3)", "coded_matmul_kernel.1", "pad.3",
       "fusion.2", "copy.1")}}}
planes {{ id: 3 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 }} }}
"""


def test_reduction_of_a_hand_written_trace():
    from jax.profiler import ProfileData
    s = trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert s.n_chips == 1                 # TPU:1 ran nothing
    assert s.window_s == pytest.approx(100e-6)
    # union of [10, 30] and [25, 40], [60, 70], and [95, 110] cut at 100
    assert s.busy_s == pytest.approx(45e-6)
    assert s.op_s == pytest.approx({
        "jit__round:coded_matmul_kernel.1": 20e-6, "jit__round:pad.3": 15e-6,
        "jit_step:fusion.2": 10e-6, "?:copy.1": 5e-6})
    assert s.op_seconds("coded_matmul_kernel") == pytest.approx(20e-6)
    assert s.op_seconds("no_such_kernel") is None
    assert s.module_runs == {"jit__round": 1, "jit_step": 1}
    s.check_complete("jit__round", 1)
    with pytest.raises(ValueError, match="lost events"):
        s.check_complete("jit_step", 2)
    # gaps [0, 10], [40, 60] and [70, 95], named at their midpoints
    assert s.gap_s == pytest.approx({"bench.round": 10e-6,
                                     "bench.round/np.asarray": 20e-6,
                                     "plan_round": 25e-6})
    b = s.breakdown(top=2)
    assert [n for n, _ in b["device_ops"]] == [
        "jit__round:coded_matmul_kernel.1", "jit__round:pad.3"]
    assert [n for n, _ in b["idle_gaps"]] == ["plan_round",
                                              "bench.round/np.asarray"]


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    text = SYNTHETIC.replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_profile(ProfileData.from_text_proto(text))


RECORDED = BENCH / "testdata" / "fig3-round-3.xplane.pb"


def test_reduction_of_a_recorded_chip_trace():
    # three fig3 plain rounds traced on a TPU v5 lite (bench.window around
    # them); the values below are this reduction's, pinned
    s = trace.reduce_file(RECORDED)
    assert s.n_chips == 1
    assert s.window_s == pytest.approx(0.074057095, rel=1e-9)
    assert s.busy_s == pytest.approx(0.023129434, rel=1e-9)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.687681052, rel=1e-8)
    assert s.op_seconds("coded_matmul_kernel") == pytest.approx(
        0.018397205, rel=1e-9)
    assert s.op_s["jit__round:berrut_encode_kernel.1"] == pytest.approx(
        0.000519299, rel=1e-9)
    assert len(s.op_s) == 36
    assert s.module_runs == {"jit__round": 3}
    # every idle nanosecond is attributed, the host's copy of the product
    # (its detiling transpose) the largest share
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s,
                                                  rel=1e-9)
    gaps = s.breakdown()["idle_gaps"]
    assert gaps[0][0] == "bench.round/Transpose::ExecuteChunk"
    assert gaps[0][1] == pytest.approx(0.047676082, rel=1e-9)
    assert gaps[1][0] == "bench.round/PythonRefManager::CollectGarbage"
