"""Uncoded work over the traced stretch against the chip's bf16 peak:
2 m d n operations for each round completed in the stretch, over the
stretch's length, in percent.  Coding's own extra work does not count."""


def read(m):
    if m is None or m["kind"] != "round" or not m["units"]:
        return None
    flops = m["uncoded_flops"] * m["units"]
    return 100.0 * flops / m["summary"].window_s / m["peaks"]["bf16_flop_s"]
