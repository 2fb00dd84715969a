"""edge_sync_ms: per cloud interval, the milliseconds of device self time in
the edge aggregations (scope ``hierfavg.sync.edge``, its codec apart), mean
over the cell's chips (``bench/scopes.py``). Nothing to read where no op
carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "edge_sync_ms")
