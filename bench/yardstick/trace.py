"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time, time per device operation, and what the host
was doing in each idle gap.

The traced stretch is the host span ``bench.window`` that the harness opens
around it.  Busy time is the union of the intervals in which an operation
ran on a chip ("XLA Ops" line of each ``/device:TPU:<i>`` plane), clipped
to that span and averaged over the chips that ran anything.  An idle gap is
named after the innermost host event covering its midpoint, prefixed with
the innermost ``bench.`` span around it, so a gap reads as
``bench.round/<what the program was doing>``.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def op_name(event_name: str) -> str:
    """An "XLA Ops" event is named by its whole HLO instruction
    (``%fusion.3 = f32[...] fusion(...), ...``); keep the instruction's
    name alone."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class TraceSummary:
    window_s: float                  # length of the traced stretch
    busy_s: float                    # device busy seconds, mean over chips
    n_chips: int                     # chips that ran an operation
    op_s: Dict[str, float]           # "<program>:<op>" -> seconds per chip
    gap_s: Dict[str, float]          # host activity -> idle seconds per chip
    module_runs: Dict[str, int] = dataclasses.field(default_factory=dict)
                                     # program -> runs overlapping the window

    def check_complete(self, program: str, runs: int) -> None:
        """Raise unless the trace holds ``runs`` runs of ``program``: the
        profiler drops events once its buffer is full, and a trace that
        lost them reads the device as idle."""
        if self.module_runs.get(program, 0) < runs:
            raise ValueError(f"the trace holds {self.module_runs.get(program, 0)}"
                             f" runs of {program}, not {runs}: it lost events")

    def op_seconds(self, op: str) -> Optional[float]:
        """Seconds of every operation whose own name (after the program
        name) starts with ``op``; None when the trace has none."""
        hits = [s for name, s in self.op_s.items()
                if name.split(":", 1)[-1].startswith(op)]
        return sum(hits) if hits else None

    def breakdown(self, top: int = 10) -> dict:
        def largest(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": largest(self.op_s),
                "idle_gaps": largest(self.gap_s)}


def find_xplane(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def _union(intervals: List[Tuple[float, float]]):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _window(host_lines) -> Tuple[float, float]:
    spans = [(s, e) for line in host_lines for s, e, name in _events(line)
             if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(spans)}")
    return spans[0]


def _gap_names(gaps, host_events):
    """Name each gap (start, end) by the host events covering its midpoint:
    a sweep over midpoints with the events active at each one."""
    evs = sorted(host_events)
    starts = [e[0] for e in evs]
    names = []
    active: list = []                 # heap of (end, -start, name)
    i = 0
    for mid in sorted((s + e) / 2 for s, e in gaps):
        j = bisect.bisect_right(starts, mid)
        for s, e, name in evs[i:j]:
            heapq.heappush(active, (e, -s, name))
        i = j
        while active and active[0][0] < mid:
            heapq.heappop(active)
        inner = span = None
        for e, neg_s, name in active:
            dur = e + neg_s
            if name.startswith(SPAN_PREFIX):
                if name != WINDOW_SPAN and (span is None or dur < span[0]):
                    span = (dur, name)
            elif inner is None or dur < inner[0]:
                inner = (dur, name)
        parts = [p[1] for p in (span, inner) if p is not None]
        names.append((mid, "/".join(parts) or "(no host event)"))
    return dict(names)


def reduce_profile(pdata) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    planes = list(pdata.planes)
    host_lines = [l for p in planes if p.name.startswith("/host:")
                  for l in p.lines]
    t0, t1 = _window(host_lines)
    host_events = [ev for line in host_lines for ev in _events(line)
                   if ev[1] >= t0 and ev[0] <= t1]

    busy, ops, gaps, chips, runs = 0.0, {}, {}, 0, {}
    for plane in planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {l.name: l for l in plane.lines}
        if "XLA Ops" not in lines:
            continue
        modules = sorted(_events(lines["XLA Modules"])) \
            if "XLA Modules" in lines else []
        mod_starts = [m[0] for m in modules]
        for s, e, name in modules:
            if e >= t0 and s <= t1:         # overlaps: the clocks differ by µs
                prog = _SUFFIX.sub("", name)
                runs[prog] = runs.get(prog, 0) + 1
        intervals = []
        for s, e, name in _events(lines["XLA Ops"]):
            s, e = max(s, t0), min(e, t1)
            if e <= s:
                continue
            intervals.append((s, e))
            k = bisect.bisect_right(mod_starts, s) - 1
            prog = (_SUFFIX.sub("", modules[k][2])
                    if k >= 0 and modules[k][1] >= s else "?")
            key = f"{prog}:{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        if not intervals:
            continue
        chips += 1
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9
        holes, prev = [], t0
        for s, e in merged:
            if s > prev:
                holes.append((prev, s))
            prev = max(prev, e)
        if prev < t1:
            holes.append((prev, t1))
        named = _gap_names(holes, host_events)
        for s, e in holes:
            name = named[(s + e) / 2]
            gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9
    if chips == 0:
        raise ValueError("no device operation ran inside the traced window")
    scale = 1.0 / chips
    return TraceSummary(
        window_s=(t1 - t0) * 1e-9, busy_s=busy * scale, n_chips=chips,
        op_s={k: v * scale for k, v in ops.items()},
        gap_s={k: v * scale for k, v in gaps.items()},
        module_runs={k: -(-v // chips) for k, v in runs.items()})


def reduce_file(path) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))
