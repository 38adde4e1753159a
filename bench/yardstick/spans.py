"""Reduction of the program's own host spans (``spacdc.<name>``, written
by ``repro.runtime.spans``) in a traced run's profiler trace, inside the
harness's ``bench.window`` span.

For each span name: its count, total and self seconds (self: less the
part covered by ``spacdc.`` spans nested in it on the same thread), and
the sums of its numeric counters.  The device's idle time, its holes found
as ``trace.py`` finds them, is split by overlap over the innermost (the
shortest) ``spacdc.`` span in force at each instant, and the idle under no
such span is kept apart; both are seconds per chip, averaged over the
chips that ran anything.

A per-layer metric receives only ``measure``, which holds no path, so
:func:`for_measure` finds the trace itself: the newest ``.xplane.pb``
under ``<checkout>/.bench_trace/``, accepted only if its ``bench.window``
is exactly as long as ``measure["summary"].window_s``.  The reduction is
memoised, so the metrics of one run parse the trace once.  A trace that
holds no ``spacdc.`` span (a program that records none) reduces to empty
tables, and the metrics read nothing there.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
from pathlib import Path
from typing import Dict, List, Optional

from . import trace

PREFIX = "spacdc."
CHECKOUT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class SpanSummary:
    window_s: float                  # length of the traced stretch
    count: Dict[str, int]            # span name -> spans in the stretch
    total_s: Dict[str, float]        # span name -> summed duration
    self_s: Dict[str, float]         # span name -> duration less children
    counters: Dict[str, Dict[str, float]]   # span name -> counter -> sum
    idle_s: Dict[str, float]         # innermost span name -> device idle
    idle_unspanned_s: float          # device idle under no spacdc. span

    def ms_per(self, name: str, unit: str) -> Optional[float]:
        """Total milliseconds of span ``name`` per ``unit`` span; None
        when the stretch holds either none of them."""
        if not self.count.get(name) or not self.count.get(unit):
            return None
        return 1e3 * self.total_s[name] / self.count[unit]


def _innermost(spans, t0, t1):
    """[start, end, name] pieces that partition [t0, t1] by the innermost
    span in force (None where none is)."""
    evs = sorted(spans)
    cuts = sorted({t0, t1, *(s for s, _, _ in evs), *(e for _, e, _ in evs)})
    pieces, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(evs) and evs[i][0] <= a:
            s, e, name = evs[i]
            heapq.heappush(active, (e - s, e, name))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        name = active[0][2] if active else None
        if pieces and pieces[-1][2] == name:
            pieces[-1][1] = b
        else:
            pieces.append([a, b, name])
    return pieces


def _split(holes, pieces, out: Dict[Optional[str], float]) -> None:
    """Add each hole's overlap with each piece to ``out[piece name]``, in
    seconds (both lists sorted, neither overlapping itself)."""
    j = 0
    for s, e in holes:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            lo, hi = max(s, pieces[k][0]), min(e, pieces[k][1])
            if hi > lo:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) \
                    + (hi - lo) * 1e-9
            k += 1


def _self_times(line_spans, self_ns: Dict[str, float]) -> None:
    """Add each span's duration less its direct children's, on one
    thread, to ``self_ns[name]``."""
    stack: List[tuple] = []
    for s, e, name in sorted(line_spans, key=lambda x: (x[0], -x[1])):
        while stack and not (stack[-1][0] <= s and e <= stack[-1][1]):
            stack.pop()
        if stack:
            self_ns[stack[-1][2]] -= e - s
        self_ns[name] = self_ns.get(name, 0.0) + (e - s)
        stack.append((s, e, name))


def reduce_profile(pdata) -> SpanSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`SpanSummary`."""
    planes = list(pdata.planes)
    host_lines = [l for p in planes if p.name.startswith("/host:")
                  for l in p.lines]
    t0, t1 = trace._window(host_lines)
    count, total, self_ns, counters, spans = {}, {}, {}, {}, []
    for line in host_lines:
        mine = []
        for e in line.events:
            if not e.name.startswith(PREFIX):
                continue
            s = max(float(e.start_ns), t0)
            end = min(float(e.start_ns) + float(e.duration_ns), t1)
            if end < s:
                continue
            mine.append((s, end, e.name))
            count[e.name] = count.get(e.name, 0) + 1
            total[e.name] = total.get(e.name, 0.0) + (end - s) * 1e-9
            sums = counters.setdefault(e.name, {})
            for k, v in e.stats:
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    sums[k] = sums.get(k, 0) + v
        _self_times(mine, self_ns)
        spans += mine

    pieces = _innermost(spans, t0, t1)
    idle, chips = {}, 0
    for plane in planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        lines = {l.name: l for l in plane.lines}
        if "XLA Ops" not in lines:
            continue
        busy = [(max(s, t0), min(e, t1))
                for s, e, _ in trace._events(lines["XLA Ops"])]
        busy = [(s, e) for s, e in busy if e > s]
        if not busy:
            continue
        chips += 1
        holes, prev = [], t0
        for s, e in trace._union(busy):
            if s > prev:
                holes.append((prev, s))
            prev = max(prev, e)
        if prev < t1:
            holes.append((prev, t1))
        _split(holes, pieces, idle)
    scale = 1.0 / max(chips, 1)
    unspanned = idle.pop(None, 0.0)
    return SpanSummary(
        window_s=(t1 - t0) * 1e-9, count=count, total_s=total,
        self_s={k: v * 1e-9 for k, v in self_ns.items()},
        counters=counters,
        idle_s={k: v * scale for k, v in idle.items()},
        idle_unspanned_s=unspanned * scale)


def reduce_file(path) -> SpanSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


@functools.lru_cache(maxsize=2)
def _reduce_cached(path: str, mtime_ns: int) -> Optional[SpanSummary]:
    try:
        return reduce_file(path)
    except ValueError:                # no single bench.window span
        return None


def newest_trace(root: Path = CHECKOUT) -> Optional[Path]:
    files = list(Path(root).glob(".bench_trace/*/**/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime_ns) if files else None


def for_measure(m, root: Path = CHECKOUT) -> Optional[SpanSummary]:
    """The span reduction of the traced stretch ``m`` describes, or None
    when the newest trace in the checkout is not that stretch's."""
    if m is None or m.get("summary") is None:
        return None
    path = newest_trace(root)
    if path is None:
        return None
    s = _reduce_cached(str(path), path.stat().st_mtime_ns)
    if s is None or s.window_s != m["summary"].window_s:
        return None
    return s
