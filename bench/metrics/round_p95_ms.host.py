"""95th percentile of the per-round walls of the traced run's measured
window (untraced), on the host clock, in milliseconds.  Every round does
the same device work, so this reads host and transfer jitter; it is
recorded and decides nothing."""

import numpy as np


def read(m):
    if m is None or m["kind"] != "round" or not m["unit_walls_s"]:
        return None
    return 1e3 * float(np.percentile(m["unit_walls_s"], 95))
