"""Share of the routing choices that this chip's experts serve: the
``routed_here`` counters of the traced pass's ``spacdc.serve.step`` spans
(each step's live rows' choices that fell on a held expert, summed over
the MoE layers), summed, over their ``live`` counters summed times the
experts each row chooses and the MoE layers, in percent.  A trace without
the counter reads nothing."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spans  # noqa: E402


def read(m):
    if m is None or m["kind"] != "serve" or not m.get("moe_layers"):
        return None
    s = spans.for_measure(m)
    c = {} if s is None else s.counters.get("spacdc.serve.step", {})
    if "routed_here" not in c or not c.get("live"):
        return None
    return 100.0 * c["routed_here"] / (c["live"] * m["top_k"]
                                       * m["moe_layers"])
