"""engine_idle_ms: per cloud interval, the milliseconds in which the chip ran
no op (gaps of 20 us or more) while the engine thread was in any other
``fed.*`` span (dispatch, flush, eval, checkpoint, the store swap, the run's
own set-up), mean over the cell's chips (``bench/scopes.py``). Nothing to
read where the trace holds no engine span."""
from bench import scopes


def read(ctx):
    return scopes.idle_ms(ctx, "engine")
