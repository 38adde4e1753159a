"""Roofline share of the fused coded-round kernel: the least time its work
can take on the chip (the encode of N shards from K + T blocks and the N
worker products, counted from the shapes, over the bf16 peak; or A, the
noise blocks, B and the products moved once over HBM bandwidth, whichever
is longer), over the summed device time of ``coded_matmul_kernel`` events,
in percent.  No event, no reading."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import work  # noqa: E402


def read(m):
    if m is None or m["kind"] != "round" or not m["units"]:
        return None
    t_kernel = m["summary"].op_seconds("coded_matmul_kernel")
    if not t_kernel:
        return None
    least, _bound = work.least_time_s(
        m["kernel_work"]["coded_matmul_kernel"], m["peaks"])
    return 100.0 * least * m["units"] / t_kernel
