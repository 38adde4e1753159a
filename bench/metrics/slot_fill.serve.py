"""Useful rows over the rows the step program ran: the ``live`` counters
of the traced pass's ``spacdc.serve.step`` spans (slots serving a
request) summed, over their ``bucket`` counters (the padded batch width)
summed, in percent.  A trace without them reads nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "serve":
        return None
    s = spans.for_measure(m)
    c = {} if s is None else s.counters.get("spacdc.serve.step", {})
    if not c.get("bucket"):
        return None
    return 100.0 * c.get("live", 0) / c["bucket"]
