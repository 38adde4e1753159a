"""Device busy time per coded round in the traced stretch (the round
program: encode, worker products, masked decode, and the wire where the
cell encrypts), in milliseconds."""


def read(m):
    if m is None or m["kind"] != "round" or not m["units"]:
        return None
    return 1e3 * m["summary"].busy_s / m["units"]
