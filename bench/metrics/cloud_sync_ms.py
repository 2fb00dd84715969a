"""cloud_sync_ms: per cloud interval, the milliseconds of device self time in
the cloud aggregation (scope ``hierfavg.sync.cloud``, its codec apart; on a
mesh the cross-chip psum is inside it), mean over the cell's chips
(``bench/scopes.py``). Nothing to read where no op carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "cloud_sync_ms")
