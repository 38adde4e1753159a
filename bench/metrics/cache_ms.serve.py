"""The serve loop's eager KV-cache operations (slice, merge, the zeroing
of an admitted slot and the gather after evictions): the program's
``spacdc.serve.cache`` spans in the traced pass, summed, over its
``spacdc.serve.step`` spans, in milliseconds.  A trace without them reads
nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "serve":
        return None
    s = spans.for_measure(m)
    return None if s is None else s.ms_per("spacdc.serve.cache",
                                           "spacdc.serve.step")
