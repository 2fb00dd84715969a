"""collective_exposed_ms: per cloud interval, the milliseconds of cross-chip
collective ops during which no other op ran on that chip, mean over the
cell's chips. Nothing to read where the trace holds no collective."""


def read(ctx):
    t = ctx["trace"]
    if not t["collective_ops"]:
        return None
    return 1000.0 * t["collective_exposed_s"] / ctx["intervals"]
