"""Plain forward work of the served model over the traced pass against the
chip's bf16 peak: the operations of every token position the pass fed
(prompt tokens and generated tokens, padded rows left out), over the
pass's length, in percent.  Coding's own extra work does not count."""


def read(m):
    if m is None or m["kind"] != "serve" or not m["units"]:
        return None
    return (100.0 * m["model_flops"] / m["summary"].window_s
            / m["peaks"]["bf16_flop_s"])
