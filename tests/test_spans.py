"""The program's host spans (``repro.runtime.spans``) as the profiler
records them: one ``spacdc.round`` per coded round with its inner spans,
one ``spacdc.serve.step`` per loop step with its counters, a
``spacdc.trace`` span only where a program traced, outputs that do not
depend on whether a profiler is running, and every call site naming a
listed span with only the counters a reader uses."""

import ast
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import (ClusterSpec, CodeSpec, PrivacySpec, ServeSpec,
                       Session, StragglerSpec)
from repro.runtime.serve_loop import Request
from repro.runtime.spans import SPANS

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
# the counters a span may carry, each read by a benchmark metric or a test
COUNTERS = {"bucket", "live", "new_bucket", "fn", "chunked", "bytes"}

SPEC = ClusterSpec(
    code=CodeSpec(scheme="spacdc", n_workers=10, k_blocks=4),
    privacy=PrivacySpec(t_colluding=1, noise_scale=0.05),
    straggler=StragglerSpec(n_stragglers=2),
    serve=ServeSpec(coded_layers="all", max_slots=2), seed=3)

# (prompt, generation) lengths of the backlog: 3 requests over 2 slots
LENGTHS = [(4, 3), (3, 2), (5, 2)]


def _operands(m, d=16, n=8, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((m, d)).astype(np.float32),
            rng.standard_normal((d, n)).astype(np.float32))


def _backlog():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(1, 256, p).astype(np.int32),
                    gen=g) for i, (p, g) in enumerate(LENGTHS)]


def _profiled(log_dir, fn):
    """(fn(), every ``spacdc.`` host event as (line, start, end, name,
    stats)) with a profiler session around the call."""
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spacdc."):
                    events.append((line.name, e.start_ns,
                                   e.start_ns + e.duration_ns, e.name,
                                   dict(e.stats)))
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(events, outer, name):
    """The events named ``name`` that lie within ``outer``, on its line."""
    line, s, e = outer[:3]
    return [ev for ev in events if ev[3] == name and ev[0] == line
            and s <= ev[1] and ev[2] <= e]


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
    """Three profiled rounds (the first compiles, the second repeats its
    shape, the third is a new shape) and one unprofiled replay of the
    first in a fresh session."""
    a, b = _operands(48)
    a2, b2 = _operands(96, seed=1)

    def run():
        with Session(SPEC) as s:
            return [s.matmul(a, b, round_idx=0)[0],
                    s.matmul(a, b, round_idx=1)[0],
                    s.matmul(a2, b2, round_idx=2)[0]]

    outs, events = _profiled(tmp_path_factory.mktemp("rounds"), run)
    with Session(SPEC) as s:
        plain = s.matmul(a, b, round_idx=0)[0]
    return outs, events, plain


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The backlog served twice under the profiler by one batcher (the
    first pass compiles every bucket, the second none) and once without
    it by a fresh session's."""
    def run():
        with Session(SPEC) as s:
            bat = s.batcher(tiny=True)
            return bat.run(_backlog()), bat.run(_backlog())

    (first, second), events = _profiled(tmp_path_factory.mktemp("serve"),
                                        run)
    with Session(SPEC) as s:
        plain = s.batcher(tiny=True).run(_backlog())
    return first, second, events, plain


def test_fused_round_holds_one_of_each_inner_span(rounds):
    _, events, _ = rounds
    top = [ev for ev in events if ev[3] == "spacdc.round"]
    assert len(top) == 3 and all(ev[4] == {} for ev in top)
    for outer in top:
        for name in ("plan", "dispatch", "wait", "to_host"):
            assert len(_inside(events, outer, f"spacdc.round.{name}")) == 1


def test_copy_to_host_carries_the_product_bytes_and_its_layout(rounds):
    _, events, _ = rounds
    copies = [ev[4] for ev in events if ev[3] == "spacdc.round.to_host"]
    # the rounds' products, 48 x 8 twice, then 96 x 8, each leaving the
    # round program whole (row chunks only from 32 MB on, and on a TPU)
    want = [{"bytes": 4 * m * n, "chunked": 0}
            for m, n in ((48, 8), (48, 8), (96, 8))]
    assert copies == want


def test_trace_span_marks_only_the_rounds_that_compiled(rounds):
    _, events, _ = rounds
    disp = [ev for ev in events if ev[3] == "spacdc.round.dispatch"]
    traced = [[t[4] for t in _inside(events, d, "spacdc.trace")]
              for d in disp]
    assert traced == [[{"fn": "fused_round"}], [], [{"fn": "fused_round"}]]


def test_one_step_span_per_step_with_the_backlog_slot_fill(served):
    first, second, events, _ = served
    steps = [ev for ev in events if ev[3] == "spacdc.serve.step"]
    assert len(steps) == first.n_steps + second.n_steps
    for res, mine in ((first, steps[:first.n_steps]),
                      (second, steps[first.n_steps:])):
        assert [s[4]["bucket"] for s in mine] == res.buckets.tolist()
        # every live slot feeds one position a step: its prompt, then all
        # but the last generated token
        assert sum(s[4]["live"] for s in mine) == sum(
            p + g - 1 for p, g in LENGTHS)
    for s in steps:
        for name in ("inputs", "plan", "wait", "to_host", "consume"):
            assert _inside(events, s, f"spacdc.serve.{name}"), name
    admits = [ev for ev in events if ev[3] == "spacdc.serve.admit"]
    assert len(admits) == 2 * len(LENGTHS)
    for a in admits:                          # each zeroes its slot
        assert len(_inside(events, a, "spacdc.serve.cache")) == 1


def test_new_bucket_dispatch_alone_holds_the_step_trace(served):
    _, _, events, _ = served
    disp = [ev for ev in events if ev[3] == "spacdc.serve.dispatch"]
    fresh = [d for d in disp if d[4]["new_bucket"] == 1]
    assert len(fresh) == 2                    # buckets 1 and 2, first pass
    for d in disp:
        traced = _inside(events, d, "spacdc.trace")
        if d[4]["new_bucket"]:
            assert [t[4] for t in traced] == [{"fn": "serve_step"}]
        else:
            assert traced == []


def test_outputs_do_not_depend_on_the_profiler(rounds, served):
    outs, _, plain_round = rounds
    np.testing.assert_array_equal(outs[0], plain_round)
    first, _, _, plain = served
    assert [r.tokens.tolist() for r in first.requests] == \
        [r.tokens.tolist() for r in plain.requests]
    np.testing.assert_array_equal(first.buckets, plain.buckets)


def test_step_end_stamps_are_monotone_one_per_step(served):
    first, second, _, plain = served
    for res in (first, second, plain):
        ends = res.step_end_wall_s
        assert ends.shape == (res.n_steps,) and res.n_steps > 0
        assert ends[0] > 0 and np.all(np.diff(ends) > 0)


def _call_sites():
    """(file, line, name, counter names) of every ``span(...)`` call in
    the program."""
    sites = []
    for path in sorted(SRC.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "span"):
                name = node.args[0]
                sites.append((path.name, node.lineno,
                              name.value if isinstance(name, ast.Constant)
                              else None, [k.arg for k in node.keywords]))
    return sites


def test_every_span_is_listed_and_carries_host_counters(rounds, served):
    sites = _call_sites()
    assert len(sites) >= len(SPANS)
    for path, line, name, keys in sites:
        assert name in SPANS, (path, line)
        assert set(keys) <= COUNTERS, (path, line)
    assert {name for _, _, name, _ in sites} == set(SPANS)
    # as recorded: every counter a host int or a string
    for events in (rounds[1], served[2]):
        for *_, name, stats in events:
            assert name[len("spacdc."):] in SPANS
            assert set(stats) <= COUNTERS
            assert all(isinstance(v, (int, str)) for v in stats.values())
