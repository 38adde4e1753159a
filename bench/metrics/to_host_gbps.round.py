"""Speed of the copy of each round's product to the host: the ``bytes``
counters of the traced stretch's ``spacdc.round.to_host`` spans summed,
over those spans' seconds summed, in GB/s.  A trace whose copies carry no
such counter reads nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "round":
        return None
    s = spans.for_measure(m)
    name = "spacdc.round.to_host"
    if s is None or "bytes" not in s.counters.get(name, {}) \
            or not s.total_s[name]:
        return None
    return 1e-9 * s.counters[name]["bytes"] / s.total_s[name]
