"""Share of the traced stretch of serving passes in which no operation ran
on the chip: 1 - (union of device-op intervals) / stretch, in percent."""


def read(m):
    if m is None or m["kind"] != "serve":
        return None
    s = m["summary"]
    return 100.0 * (1.0 - s.busy_s / s.window_s)
