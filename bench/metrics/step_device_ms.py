"""Device busy time per serving loop step in the traced pass (the jitted
step with every coded site, and the loop's eager cache updates), in
milliseconds."""


def read(m):
    if m is None or m["kind"] != "serve" or not m["units"]:
        return None
    return 1e3 * m["summary"].busy_s / m["units"]
