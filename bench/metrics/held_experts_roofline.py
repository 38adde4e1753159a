"""Roofline share of the held-experts kernel: the least time its work can
take on the chip, summed over the traced pass's calls (each call's gate,
up and down products of every held expert on the step's rows, counted
from the shapes by ``moe_work``, over the bf16 peak; or the held weights,
the rows and the output moved once over HBM bandwidth, whichever is
longer), over the summed device time of ``held_experts_kernel`` events,
in percent.  No event, no reading."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import work  # noqa: E402


def read(m):
    if m is None or m["kind"] != "serve" or "kernel_calls" not in m:
        return None
    t_kernel = m["summary"].op_seconds("held_experts_kernel")
    if not t_kernel:
        return None
    least = sum(n * work.least_time_s(w, m["peaks"])[0]
                for w, n in m["kernel_calls"]["held_experts_kernel"])
    return 100.0 * least / t_kernel
