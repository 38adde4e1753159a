"""Copy of each round's product to the host: the program's
``spacdc.round.to_host`` spans in the traced stretch, summed, over its
``spacdc.round`` spans, in milliseconds.  A trace without them reads
nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "round":
        return None
    s = spans.for_measure(m)
    return None if s is None else s.ms_per("spacdc.round.to_host",
                                           "spacdc.round")
