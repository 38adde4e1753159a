"""How often the round's product left the chip in row chunks that host
threads copy at once: the ``spacdc.round.to_host`` spans of the traced
stretch whose ``chunked`` counter is 1, over all of them, in percent.  A
trace whose copies carry no such counter reads nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "round":
        return None
    s = spans.for_measure(m)
    name = "spacdc.round.to_host"
    if s is None or "chunked" not in s.counters.get(name, {}):
        return None
    return 100.0 * s.counters[name]["chunked"] / s.count[name]
