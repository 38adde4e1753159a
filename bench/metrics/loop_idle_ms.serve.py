"""Device idle that the serve loop's host work holds: idle time in the
traced pass whose innermost program span is a ``spacdc.serve.`` span
other than ``spacdc.serve.wait`` (the wait is the host waiting on the
chip), over the pass's ``spacdc.serve.step`` spans, in milliseconds per
step.  A trace without them reads nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "serve":
        return None
    s = spans.for_measure(m)
    steps = 0 if s is None else s.count.get("spacdc.serve.step", 0)
    if not steps:
        return None
    idle = sum(v for k, v in s.idle_s.items()
               if k.startswith("spacdc.serve.") and k != "spacdc.serve.wait")
    return 1e3 * idle / steps
