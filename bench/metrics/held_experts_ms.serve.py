"""Device time of the held-experts kernel (``held_experts_kernel`` events,
one per MoE layer per step) in the traced pass, over its loop steps, in
milliseconds per step.  No event, no reading."""


def read(m):
    if m is None or m["kind"] != "serve" or not m["units"]:
        return None
    t_kernel = m["summary"].op_seconds("held_experts_kernel")
    return None if not t_kernel else 1e3 * t_kernel / m["units"]
